"""Fleet: host milliseconds the worker waited for a pooled canvas's
earlier ship (``pixie.canvas_wait`` spans) per dispatch
(``pixie.execute`` spans) in the traced window."""

import spans


def read(ctx):
    return spans.per_execute_ms(spans.window_spans(ctx), "pixie.canvas_wait")
