"""Set-up: from process start until the window opens (imports, device
start, frames, fleet, compiles and warm-up; the host clock)."""


def read(ctx):
    return ctx.setup_s
