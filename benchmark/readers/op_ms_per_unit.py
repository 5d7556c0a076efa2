"""Self seconds of the device operations whose name holds ``contains``,
inside the traced window, per unit of a sum over the window's step records
(the ``per_keys`` added, times ``per_scale``), in ms: a kernel's time per
thousand tokens it served."""


def read(ctx, contains, span, per_keys, per_scale=1.0):
    tr = ctx["trace"]
    spans = tr.span_list(span)
    steps = ctx["result"].get("steps", [])[:len(spans)]
    if not spans or not tr.devices():
        return None
    secs = tr.op_seconds(lambda name: any(c in name for c in contains))
    units = sum(s.get(k, 0) for s in steps for k in per_keys) * per_scale
    if secs == 0.0 or units == 0.0:
        return None
    return 1e3 * secs / units
