"""Share, %, of the device programs' time (``XLA Modules`` line) inside the
traced window that went to the programs whose name holds ``contains``,
summed over devices.  0 where programs ran and none had the name: in a
control cell that is the reading wanted."""


def read(ctx, contains):
    runs = ctx["trace"].modules_in_window()
    whole = sum(m.end - m.start for m in runs)
    if whole == 0.0:
        return None
    return 100.0 * sum(m.end - m.start for m in runs
                       if contains in m.name) / whole
