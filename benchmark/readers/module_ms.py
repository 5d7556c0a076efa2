"""Seconds of the device programs (``XLA Modules`` line) whose name holds
``contains``, per unit of a step-record sum (``per_key`` x ``per_scale``)
over the traced steps, in ms."""


def read(ctx, contains, span, per_key, per_scale=1.0):
    tr = ctx["trace"]
    spans = tr.span_list(span)
    steps = ctx["result"].get("steps", [])[:len(spans)]
    devs = tr.devices()
    if not spans or not devs:
        return None
    secs = sum(m.end - m.start for m in tr.modules_in_window()
               if contains in m.name)
    units = sum(s[per_key] for s in steps) * per_scale
    if secs == 0.0 or units == 0.0:
        return None
    return 1e3 * secs / len(devs) / units
