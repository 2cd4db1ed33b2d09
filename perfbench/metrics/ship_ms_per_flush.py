"""Fleet: host milliseconds in the host-to-device call for the canvas
(``pixie.ship`` spans) per dispatch (``pixie.execute`` spans) in the
traced window."""

import spans


def read(ctx):
    return spans.per_execute_ms(spans.window_spans(ctx), "pixie.ship")
