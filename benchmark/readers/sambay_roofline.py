"""Share of its roofline one of the Phi-4-mini-flash kernels reaches, %: the
least time the chip could take for the window's calls (the larger of
operations / peak and bytes / peak, counted by ``sambay_counts.py`` from the
program's per-step counters and the configuration's widths) over the kernel's
measured device time.  ``what``: ``ssm_step``, ``ssm_chunk``,
``window_decode`` or ``shared_pages`` (the paged decode kernel over the one
pool layer, times the layers that read it).  No such kernel in the trace, or
no such counter in the step records (a parent commit), is no reading."""

from benchmark import roofline, sambay_counts


def layers(desc, kind):
    return sum(n * period.count(kind) for period, n in desc["runs"])


def least_seconds(what, steps, desc, engine, peaks):
    """-> the roofline's seconds for the step records' counters, or None
    where they hold none."""
    def total(key):
        return sum(s.get(key, 0) for s in steps)

    inner, state = desc["ssm_inner"], desc["ssm_state"]
    heads = (desc["num_attention_heads"], desc["num_key_value_heads"],
             desc["head_dim"])
    if what == "ssm_step":
        if not any("ssm_rows" in s for s in steps):
            return None
        n = layers(desc, "mamba")
        ops, nbytes = sambay_counts.ssm_step_ops_bytes(
            n * total("ssm_rows"),
            n * sum(1 for s in steps if s.get("ssm_rows")), inner, state)
    elif what == "ssm_chunk":
        n = layers(desc, "mamba")
        ops, nbytes = sambay_counts.ssm_chunk_ops_bytes(
            n * (total("chunk_tokens") + total("recompute_tokens")),
            n * total("chunks"), inner, state)
    elif what == "window_decode":
        if not any("window_tokens" in s for s in steps):
            return None
        ops, nbytes = sambay_counts.attend_ops_bytes(
            layers(desc, "swa") * total("window_tokens"), *heads)
    elif what == "shared_pages":
        if not any("shared_kv_pages" in s for s in steps):
            return None
        readers = layers(desc, "dattn") + layers(desc, "xattn")
        ops, nbytes = sambay_counts.attend_ops_bytes(
            readers * total("shared_kv_pages") * engine["page_size"], *heads)
    else:
        raise ValueError(f"unknown kernel count {what!r}")
    return roofline.roofline_seconds(ops, nbytes, peaks)[0]


def read(ctx, what, kernel, span):
    tr, res = ctx["trace"], ctx["result"]
    spans = tr.span_list(span)
    steps = res.get("steps", [])[:len(spans)]
    got = tr.op_seconds(lambda name: kernel in name)
    if not spans or got == 0.0 or "runs" not in res["desc"]:
        return None
    least = least_seconds(what, steps, res["desc"], res["engine_config"],
                          roofline.peaks(ctx["device"]["kind"]))
    return None if least is None else 100.0 * least / got
