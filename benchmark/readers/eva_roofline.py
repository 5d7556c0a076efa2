"""Share of its roofline EVA attention reaches in one of the two serving
programs, %: the least time the chip could take for the window's work (the
larger of operations / peak and bytes / peak, counted by ``eva_counts.py``
from the program's per-step counters and the configuration's shapes, whatever
implements it) over the measured device time of the kernels named.  ``what``:
``decode`` (the decode program's attention: ``eva_rows_attended`` and
``decode_rows``) or ``chunk`` (the chunk program's: the step records'
``chunk_tokens``, ``ctx_tokens`` and the per-call products the generator kept
of the program's ``prefill`` spans).  No such kernel in the trace, or no such
counter in the step records (a parent commit, another family), is no
reading."""

from benchmark import eva_counts, roofline


def least_seconds(what, steps, desc, n_layers, peaks):
    """-> (the roofline's seconds, "memory" | "compute") for the step
    records' counters, or None where they hold none."""
    shape = (n_layers, desc["num_attention_heads"], desc["head_dim"])

    def total(key):
        return sum(s.get(key, 0) for s in steps)

    if what == "decode":
        if not any("eva_rows_attended" in s for s in steps):
            return None
        ops, nbytes = eva_counts.eva_decode_ops_bytes(
            total("eva_rows_attended"), total("decode_rows"), *shape)
    elif what == "chunk":
        if not any("eva_chunk_tokens_x_ctx" in s for s in steps):
            return None
        ops, nbytes = eva_counts.eva_chunk_ops_bytes(
            total("eva_chunk_tokens"), total("eva_chunk_tokens_x_ctx"),
            total("eva_chunk_causal_pairs"), total("ctx_tokens"), *shape)
    else:
        raise ValueError(f"unknown count {what!r}")
    return roofline.roofline_seconds(ops, nbytes, peaks)


def read(ctx, what, kernel, span):
    tr, res = ctx["trace"], ctx["result"]
    spans = tr.span_list(span)
    steps = res.get("steps", [])[:len(spans)]
    got = tr.op_seconds(lambda name: kernel in name)
    if not spans or got == 0.0 or "window_size" not in res["desc"]:
        return None
    least = least_seconds(what, steps, res["desc"], res["n_layers"],
                          roofline.peaks(ctx["device"]["kind"]))
    return None if least is None or not least[0] else 100.0 * least[0] / got
