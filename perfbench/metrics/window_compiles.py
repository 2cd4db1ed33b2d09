"""Plan compiler: executables built (JAX backend-compile events, cache
hits included) from the window's opening until its last answer."""


def read(ctx):
    return float(ctx.window_compiles)
