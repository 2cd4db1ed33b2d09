"""Fleet: host milliseconds spent zeroing the canvas and copying the
frames into it (``pixie.embed`` spans) per dispatch (``pixie.execute``
spans) in the traced window."""

import spans


def read(ctx):
    return spans.per_execute_ms(spans.window_spans(ctx), "pixie.embed")
