"""Share of its roofline the latent-attention decode kernel reaches, %: the
least time the chip could take for the window's calls (the larger of
operations / peak and bytes / peak, counted by ``mla_counts.py`` from the
program's per-step counter ``latent_kv_tokens`` and the configuration's
widths) over the kernel's measured device time.  At 57.6 operations a byte
against the chip's 240 the bytes set it (``bound`` says which did).  No such
kernel in the trace, or no such counter in the step records (a parent
commit), is no reading."""

from benchmark import mla_counts, roofline


def bound(steps, desc, n_layers, peaks):
    """-> (the roofline's seconds, "memory" | "compute") for the step
    records' counters, or None where they hold none."""
    if not any("latent_kv_tokens" in s for s in steps):
        return None
    ops, nbytes = mla_counts.mla_decode_ops_bytes(
        sum(s.get("latent_kv_tokens", 0) for s in steps),
        sum(s.get("decode_rows", 0) for s in steps), n_layers,
        desc["num_attention_heads"], desc["kv_lora_rank"],
        desc["qk_rope_head_dim"])
    return roofline.roofline_seconds(ops, nbytes, peaks)


def read(ctx, kernel, span):
    tr, res = ctx["trace"], ctx["result"]
    spans = tr.span_list(span)
    steps = res.get("steps", [])[:len(spans)]
    got = tr.op_seconds(lambda name: kernel in name)
    if not spans or got == 0.0 or "kv_lora_rank" not in res["desc"]:
        return None
    least = bound(steps, res["desc"], res["n_layers"],
                  roofline.peaks(ctx["device"]["kind"]))
    return None if least is None else 100.0 * least[0] / got
