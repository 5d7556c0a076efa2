"""A percentile of host-clock samples the generator kept (seconds -> ms)."""

from benchmark import stats


def read(ctx, key, q):
    samples = ctx["result"].get(key)
    if not samples:
        return None
    return 1e3 * stats.percentile(samples, q)
