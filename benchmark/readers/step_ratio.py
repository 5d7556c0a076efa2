"""Ratio of two sums over the traced window's step records: ``over`` added
up, divided by ``under`` added up.  A record without the keys (a program that
counts no such thing) gives no reading."""


def read(ctx, span, over, under):
    spans = ctx["trace"].span_list(span)
    steps = ctx["result"].get("steps", [])[:len(spans)]
    if not any(over in s and under in s for s in steps):
        return None
    den = sum(s.get(under, 0) for s in steps)
    return sum(s.get(over, 0) for s in steps) / den if den else None
