"""A percentile of one key over the traced window's step records (those that
hold it): the median number of state slots in use, say."""

from benchmark import stats


def read(ctx, span, key, percentile):
    spans = ctx["trace"].span_list(span)
    vals = [s[key] for s in ctx["result"].get("steps", [])[:len(spans)]
            if key in s]
    return float(stats.percentile(vals, percentile)) if vals else None
