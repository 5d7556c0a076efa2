"""Share of its roofline the paged decode kernel reaches, %: bytes of K and
V the visible pages hold (from the page geometry and each decoded row's
context, by ``roofline.py``) at the chip's HBM bandwidth, over the kernel's
measured device time."""

from benchmark import roofline


def read(ctx, kernel, span):
    tr, res = ctx["trace"], ctx["result"]
    spans = tr.span_list(span)
    steps = res.get("steps", [])[:len(spans)]
    got = tr.op_seconds(lambda name: kernel in name)
    if not spans or got == 0.0:
        return None
    d, e = res["desc"], res["engine_config"]
    peak = roofline.peaks(ctx["device"]["kind"])
    pages = sum(s["decode_pages"] for s in steps)
    nbytes = res["n_layers"] * roofline.paged_decode_bytes(
        pages, e["page_size"], d["num_key_value_heads"], d["head_dim"])
    return 100.0 * (nbytes / peak["hbm_bytes_per_s"]) / got
