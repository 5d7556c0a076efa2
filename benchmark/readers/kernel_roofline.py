"""Share of its roofline one of the Solar-Open2 kernels reaches, %: the least
time the chip could take for the window's calls (the larger of operations /
peak and bytes / peak, counted by ``kernel_counts.py`` from the program's
per-step counters and the configuration's widths) over the kernel's measured
device time.  ``what``: ``experts`` (the grouped matmul over the expert
share), ``kda_step``, ``kda_chunk``, or ``paged`` (the paged decode kernel
over the layers of the period that keep pages: ``paged_roofline.py`` counts
every layer of the model).  No such kernel in the trace, or no
such counter in the step records (a parent commit), is no reading."""

from benchmark import kernel_counts, roofline


def read(ctx, what, kernel, span):
    tr, res = ctx["trace"], ctx["result"]
    spans = tr.span_list(span)
    steps = res.get("steps", [])[:len(spans)]
    got = tr.op_seconds(lambda name: kernel in name)
    if not spans or got == 0.0:
        return None
    d = res["desc"]

    def total(key):
        return sum(s.get(key, 0) for s in steps)

    if what == "experts":
        if not any("moe_experts_touched" in s for s in steps):
            return None
        ops, nbytes = kernel_counts.expert_ffn_ops_bytes(
            total("moe_local_picks"), total("moe_experts_touched"),
            d["hidden_size"], d["expert_width"])
    elif what == "paged":
        e = res["engine_config"]
        layers = (res["n_layers"] // len(d["period"])) * d["period"].count(
            "gqa")
        ops, nbytes = kernel_counts.paged_decode_ops_bytes(
            layers * total("decode_pages"), e["page_size"],
            d["num_attention_heads"], d["num_key_value_heads"], d["head_dim"])
    else:
        layers = (res["n_layers"] // len(d["period"])) * d["period"].count(
            "kda")
        heads, dim = d["kda_heads"], d["kda_head_dim"]
        if what == "kda_step":
            ops, nbytes = kernel_counts.kda_step_ops_bytes(
                layers * total("decode_rows"), heads, dim, dim)
        elif what == "kda_chunk":
            ops, nbytes = kernel_counts.kda_chunk_ops_bytes(
                layers * (total("chunk_tokens") + total("recompute_tokens")),
                layers * total("chunks"), heads, dim, dim)
        else:
            raise ValueError(f"unknown kernel count {what!r}")
    least, _bound = roofline.roofline_seconds(
        ops, nbytes, roofline.peaks(ctx["device"]["kind"]))
    return 100.0 * least / got
