"""A number the run already counted: a key of the generator's result or of
the device record, times ``scale``."""


def read(ctx, source, key, scale=1.0):
    table = ctx["device"] if source == "device" else ctx["result"]
    value = table.get(key)
    return None if value is None else value * scale
