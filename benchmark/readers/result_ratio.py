"""Ratio of two numbers the generator put on its result: ``over`` /
``under``.  A result without them (a program that counts no such thing), or a
zero below, gives no reading."""


def read(ctx, over, under):
    res = ctx["result"]
    if res.get(over) is None or not res.get(under):
        return None
    return res[over] / res[under]
