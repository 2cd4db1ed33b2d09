"""Device: 95th percentile, over the traced window's dispatches, of the
wait from the end of a dispatch's enqueue (its ``pixie.execute`` span)
to its kernel's start on the device (the ``tpu_custom_call`` op), both
on the profiler's clock."""

import spans


def read(ctx):
    if ctx.trace is None:
        return None
    return spans.p95(spans.device_queue_ms(spans.window_spans(ctx),
                                           ctx.trace["ops"]))
