"""Share of their roofline the three grouped expert matmul kernels of a
trained expert share reach, %: the least time the chip could take for the
window's picks (``moe_train_counts.expert_train_ops_bytes``: operations of the
real picks in the three passes, a lower bound on the bytes, the larger of
operations / peak and bytes / peak) over the kernels' measured device time.
No such kernel in the trace, or no counters on the result (a parent commit),
is no reading."""

from benchmark import moe_train_counts, roofline


def read(ctx, kernel):
    tr, res = ctx["trace"], ctx["result"]
    got = tr.op_seconds(lambda name: kernel in name)
    if got == 0.0 or "moe" not in res:
        return None
    d = res["desc"]
    ops, nbytes = moe_train_counts.expert_train_ops_bytes(
        res["moe"]["picks"], d["hidden_size"], d["expert_width"])
    least, _bound = roofline.roofline_seconds(
        ops, nbytes, roofline.peaks(ctx["device"]["kind"]))
    return 100.0 * least / got
