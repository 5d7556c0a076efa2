"""Share of its roofline one attention kernel of a stack of full and window
layers reaches, %, where the layers' QUERY head counts follow their type: the
least time the chip could take for the window's calls (the larger of
operations / peak and bytes / peak, counted by ``typed_gqa_counts.py`` from
the program's per-step counters and the configuration's widths by type) over
the kernel's measured device time.  ``what``: ``paged`` (the full layers'
decode over pages, from ``full_kv_tokens``), ``window`` (the window layers'
decode over rings, from ``window_kv_tokens``) or ``flash`` (the chunk
program's calls, from each chunk's ``tokens`` and ``ctx_tokens``).  No such
kernel in the trace, no such counter in the step records, or a description
without head counts by type (another family, a parent commit) is no
reading."""

from benchmark import roofline
from benchmark import typed_gqa_counts as counts

_COUNTER = {"paged": "full_kv_tokens", "window": "window_kv_tokens",
            "flash": "chunk_spans"}


def bound(what, steps, desc, peaks):
    """-> (the roofline's seconds, "memory" | "compute") for the step
    records' counters, or None where they hold none."""
    if "heads_full" not in desc or not any(_COUNTER[what] in s
                                           for s in steps):
        return None
    if what == "flash":
        ops, nbytes = counts.flash_ops_bytes(
            desc, [c for s in steps for c in s.get("chunk_spans", [])])
    else:
        ops, nbytes = counts.decode_ops_bytes(
            desc, what == "window",
            sum(s.get(_COUNTER[what], 0) for s in steps),
            sum(s.get("decode_rows", 0) for s in steps))
    return roofline.roofline_seconds(ops, nbytes, peaks)


def read(ctx, what, kernel, span):
    tr, res = ctx["trace"], ctx["result"]
    spans = tr.span_list(span)
    steps = res.get("steps", [])[:len(spans)]
    got = tr.op_seconds(lambda name: kernel in name)
    if not spans or got == 0.0:
        return None
    least = bound(what, steps, res["desc"],
                  roofline.peaks(ctx["device"]["kind"]))
    return None if least is None or least[0] == 0.0 \
        else 100.0 * least[0] / got
