"""Device: the share of the traced window in which no operation ran on
the device (one minus the union of op intervals over the window),
averaged over the chips used."""


def read(ctx):
    if ctx.trace is None or not ctx.trace["devices"]:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace_window_s)
