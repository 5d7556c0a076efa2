"""Model FLOP/s utilisation, %: the benchmark's operations per token (head
once, causal attention, no recomputation) x the traced window's tokens per
second per chip / the chip's bf16 peak."""

from benchmark import roofline


def read(ctx):
    res = ctx["result"]
    peak = roofline.peaks(ctx["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * res["flops_per_token"] * res["total_rate"] / peak
