"""Window time in which no operation ran on the device, per event of
``per_span`` (a step), in ms: what the host adds between device programs."""


def read(ctx, per_span):
    tr = ctx["trace"]
    n = len(tr.span_list(per_span))
    if not n or not tr.devices():
        return None
    return 1e3 * (tr.window_seconds() - tr.busy_seconds()) / n
