"""Share of its roofline the mixing of a residual of several streams reaches,
%: the least time the chip's memory could take to move what the mixing must
move for the window's tokens (``mhc_counts.py``: from the step records'
``chunk_tokens`` and ``decode_rows`` and the configuration's widths, NOT from
what implements the mixing) over the device time of the operations the
program wrote in its ``mhc`` region (``part_ms``'s tables and trace).  No
table, no operation of that part, a description without streams (another
family) or step records without the counters is no reading."""

from benchmark import manifest, mhc_counts, roofline

PART = "mhc"
_TOKENS = ("chunk_tokens", "recompute_tokens", "decode_rows")


def least_seconds(steps, desc, n_layers, peaks):
    """-> the seconds the memory's peak needs for the records' tokens, or
    None where the description has no streams or the records no tokens."""
    if desc.get("hc_mult", 1) <= 1 or not any(k in s for s in steps
                                              for k in _TOKENS[::2]):
        return None
    tokens = sum(s.get(k, 0) for s in steps for k in _TOKENS)
    return mhc_counts.mhc_stream_bytes(
        tokens, n_layers, desc["hc_mult"], desc["hidden_size"]) \
        / peaks["hbm_bytes_per_s"]


def read(ctx, span):
    tr, res = ctx["trace"], ctx["result"]
    ops = manifest.Manifest().module("readers", "part_ms")._load(ctx)
    n_dev = len(tr.devices())
    spans = tr.span_list(span)
    if ops is None or not n_dev or not spans:
        return None
    got = sum(o.self_s for o in ops if o.part == PART) / n_dev
    least = least_seconds(res.get("steps", [])[:len(spans)], res["desc"],
                          res["n_layers"],
                          roofline.peaks(ctx["device"]["kind"]))
    return None if not got or not least else 100.0 * least / got
