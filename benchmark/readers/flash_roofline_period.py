"""``flash_roofline.py`` over the layers that attend: the share of its
roofline the flash-attention training kernels reach, %, where only the layers
the description names ``attends`` (among its ``layer_types``) call them —
``flash_roofline.py`` multiplies by every layer of the model."""

from benchmark import roofline


def read(ctx, kernels, per_span, attends):
    tr, res = ctx["trace"], ctx["result"]
    steps = len(tr.span_list(per_span))
    d = res["desc"]
    layers = d.get("layer_types", []).count(attends)
    if not steps or not layers or not tr.devices():
        return None
    peak = roofline.peaks(ctx["device"]["kind"])
    rows = res["rows"] // max(1, ctx["chips"])
    calls = layers * res["gas"] * steps
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
