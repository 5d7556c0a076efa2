"""Device-busy ms inside the benchmark spans named ``span``, mean over the
spans whose step record passes ``where`` (e.g. decode-only steps).  The i-th
span of the trace is the i-th step of the window."""


def read(ctx, span, where=None):
    tr = ctx["trace"]
    spans = tr.span_list(span)
    steps = ctx["result"].get("steps", [])
    if not spans or not tr.devices() or len(spans) > len(steps):
        return None
    busy = [tr.busy_inside(sp.start, sp.end) for sp, st in zip(spans, steps)
            if not where or all(_test(st, k, v) for k, v in where.items())]
    if not busy:
        return None
    return 1e3 * sum(busy) / len(busy)


def _test(step, key, cond):
    op, val = cond
    return {"eq": step[key] == val, "gt": step[key] > val,
            "ge": step[key] >= val}[op]
