"""Device: of the device's idle time between ops in the traced window,
the share that falls inside a front-end flush (a ``pixie.flush`` span),
when the host, not a lack of work, held the chip back."""

import spans


def read(ctx):
    if ctx.trace is None:
        return None
    share = spans.idle_inside(spans.window_spans(ctx), ctx.trace["ops"])
    return None if share is None else 100.0 * share
