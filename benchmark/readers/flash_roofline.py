"""Share of its roofline the flash-attention training kernels reach, %: the
least time the chip could take for the calls' operations and bytes (the
larger of ops / peak FLOP/s and bytes / peak bytes/s, from shapes, by
``roofline.py``) over the kernels' measured device time."""

from benchmark import roofline


def read(ctx, kernels, per_span):
    tr, res = ctx["trace"], ctx["result"]
    steps = len(tr.span_list(per_span))
    if not steps or not tr.devices():
        return None
    d = res["desc"]
    peak = roofline.peaks(ctx["device"]["kind"])
    # one call of each kernel per layer and micro-batch, on each device's
    # share of the rows
    rows = res["rows"] // max(1, ctx["chips"])
    calls = res["n_layers"] * res["gas"] * steps
    least = measured = 0.0
    for kind, pattern in kernels.items():
        got = tr.op_seconds(lambda name, p=pattern: p in name)
        if got == 0.0:
            return None
        ops, nbytes = roofline.flash_ops_bytes(
            kind, rows, d["num_attention_heads"], d["num_key_value_heads"],
            res["seq"], d["head_dim"])
        least += calls * roofline.roofline_seconds(ops, nbytes, peak)[0]
        measured += got
    return 100.0 * least / measured
