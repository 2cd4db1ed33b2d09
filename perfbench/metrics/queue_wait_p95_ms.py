"""Front end: 95th percentile of the wait from submit until the flush
that served the request started (``LatencyStats.queue_s``), over the
window's requests."""


def read(ctx):
    q = ctx.service_latency["queue_s"]
    return q["p95"] * 1e3 if ctx.service_latency["completed"] else None
