"""The span readers, on hand-built event lists: the ``pixie.*`` spans a
served flush records, laid against the device's ops."""

from types import SimpleNamespace

import pytest

import run
import spans as sp
import trace_reduce as tr

DEV0, HOST = "/device:TPU:0", "/host:CPU"
KERNEL = "%vcgra_fused_batched.1 = custom-call(...), " + sp.KERNEL


def ev(plane, name, start_ms, dur_ms, line="python"):
    return (plane, line, name, start_ms * 1e6, dur_ms * 1e6)


def op(name, start_ms, dur_ms):
    return ev(DEV0, name, start_ms, dur_ms, line=tr.OPS_LINE)


def span(name, start_ms, dur_ms):
    return ev(HOST, name, start_ms, dur_ms)


def flush(t, seq, wait=0.0):
    """One traced flush at ``t`` ms: intake, canvas wait, embed, ship,
    execute, unpack; its kernel starts 3 ms after the enqueue ends."""
    return [span(f"pixie.flush#seq={seq},n=1#", t, 20 + wait),
            span("pixie.intake", t, 1),
            span("pixie.canvas_wait", t + 1, wait),
            span("pixie.embed", t + 1 + wait, 6),
            span("pixie.ship", t + 7 + wait, 4),
            span("pixie.execute", t + 11 + wait, 1),
            span("pixie.unpack", t + 12 + wait, 1)]


def ctx_of(events):
    return SimpleNamespace(trace=tr.reduce(events))


def run_cell(ctx, events, metric):
    """Stands in for ``run.run_cell``: the readers find the window's
    events in this frame, as they do in the harness."""
    return run.reader(metric)(ctx)


def test_spans_strip_arguments_and_keep_host_events_only():
    events = [span("pixie.flush#seq=3,n=2#", 0, 5),
              span("pixie.embed", 1, 2),
              span("XlaLinearize", 1, 1),
              ev(DEV0, "pixie.looks_like_a_span", 0, 1)]
    assert sp.spans(events) == [
        ("pixie.flush", "python", 0.0, 5e6),
        ("pixie.embed", "python", 1e6, 2e6)]


def test_end_first_matching_skips_a_backlog_of_kernels():
    # Two kernels were enqueued before tracing began; the three traced
    # dispatches own the last three kernels, in order.
    found = sp.spans(flush(0, 0) + flush(100, 1) + flush(200, 2))
    ops = tr.device_ops([op(KERNEL, -50, 10), op(KERNEL, -30, 10),
                         op("%pad.1 = pad(...)", 14, 1),
                         op(KERNEL, 15, 50), op(KERNEL, 115, 50),
                         op(KERNEL, 216, 50)])
    assert sp.device_queue_ms(found, ops) == pytest.approx([3, 3, 4])


def test_more_spans_than_kernels_reads_none():
    found = sp.spans(flush(0, 0) + flush(100, 1))
    ops = tr.device_ops([op(KERNEL, 115, 50)])
    assert sp.device_queue_ms(found, ops) is None
    assert sp.device_queue_ms([], ops) is None


def test_a_gap_half_inside_a_flush_is_half_host_bound():
    # The device idles [10, 30) ms; the flush covers [20, 45).
    found = sp.spans([span("pixie.flush", 20, 25)])
    ops = tr.device_ops([op(KERNEL, 0, 10), op(KERNEL, 30, 10)])
    assert sp.idle_inside(found, ops) == pytest.approx(0.5)
    # Two gaps, [10, 30) and [40, 50): the flush covers 10 of 30 ms.
    ops = tr.device_ops([op(KERNEL, 0, 10), op(KERNEL, 30, 10),
                         op(KERNEL, 50, 5)])
    assert sp.idle_inside(found, ops) == pytest.approx(15 / 30)
    assert sp.idle_inside([], ops) is None


@pytest.mark.parametrize("metric,want", [
    ("canvas_wait_ms_per_flush.lat", (0 + 30) / 2),
    ("embed_ms_per_flush.tput", 6 * 2 / 2),
    ("ship_ms_per_flush.lat", 4 * 2 / 2),
])
def test_per_flush_readers_divide_by_the_execute_count(metric, want):
    events = flush(0, 0) + flush(100, 1, wait=30)
    ctx = ctx_of(events + [op(KERNEL, 15, 50), op(KERNEL, 145, 50)])
    assert run_cell(ctx, events, metric) == pytest.approx(want)


def test_device_readers_through_the_harness():
    events = flush(0, 0) + flush(100, 1) + [
        op(KERNEL, 15, 50), op(KERNEL, 116, 50)]
    ctx = ctx_of(events)
    assert run_cell(ctx, events, "device_queue_ms_p95.lat") == (
        pytest.approx(3.95))
    # Idle between the kernels: [65, 116); the second flush covers
    # [100, 120) of it.
    assert run_cell(ctx, events, "idle_host_bound_pct.lat") == (
        pytest.approx(100 * 16 / 51))


@pytest.mark.parametrize("metric", [
    "canvas_wait_ms_per_flush.lat", "embed_ms_per_flush.tput",
    "ship_ms_per_flush.lat", "device_queue_ms_p95.lat",
    "idle_host_bound_pct.lat"])
def test_a_program_without_spans_reads_none(metric):
    """An older program records no ``pixie.*`` spans: each reader reads
    nothing, and so does an untraced run."""
    events = [op(KERNEL, 0, 10), op(KERNEL, 30, 10),
              span("XlaLinearize", 12, 5)]
    assert run_cell(ctx_of(events), events, metric) is None
    assert run_cell(SimpleNamespace(trace=None), None, metric) is None
