"""Frames whose output reached a client as a host array inside the
window, over the window's seconds (the client's clock)."""


def read(ctx):
    return ctx.served_in_window() / ctx.seconds
