"""Fleet bucketing: the real frame pixels served, over the canvas pixels
computed (``n_tile * Hb * Wb`` of every dispatch stamp in the window,
padded app slots included)."""


def read(ctx):
    canvas = ctx.canvas_pixels()
    return 100.0 * ctx.served_pixels() / canvas if canvas else None
