"""Self seconds of the device operations a name filter accepts, inside the
traced window, averaged over devices, per event of ``per_span``, in ms.

``contains``: the name must hold one of these (empty: any name);
``not_contains`` / ``not_collective``: what to leave out;
``exposed_only``: count collective time only where nothing else ran."""

from benchmark import trace_reduce


def read(ctx, per_span, contains=(), not_contains=(), not_collective=False,
         exposed_only=False):
    tr = ctx["trace"]
    n = len(tr.span_list(per_span))
    if not n or not tr.devices():
        return None
    if exposed_only:
        return 1e3 * tr.exposed_collective_seconds() / n

    def match(name):
        if contains and not any(c in name for c in contains):
            return False
        if any(c in name for c in not_contains):
            return False
        return not (not_collective and trace_reduce.is_collective(name))

    found = tr.op_seconds(match)
    if contains and found == 0.0:
        return None  # nothing by that name in this trace: no reading
    return 1e3 * found / n
