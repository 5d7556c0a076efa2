"""Ratio of two of the engine's decode counters as the generator kept them
on its result (``decode_stats``: what ``InferenceEngineV2.decode_stats()``
read when the window closed, counted from the end of the check — the
pre-roll and the window): ``over`` / ``under``.  The window's step records
hold only the step attributes a generator lists, and these two are not on a
list; an engine that counts no such thing (a parent commit), or a zero
below, gives no reading."""


def read(ctx, over, under):
    stats = ctx["result"].get("decode_stats") or {}
    if stats.get(over) is None or not stats.get(under):
        return None
    return stats[over] / stats[under]
