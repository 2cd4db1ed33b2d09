"""Streaming front-end + futures service API tests.

Covers the PR 6 service surface: JobHandle semantics (done/result/timeout),
the synchronous front-end's queue_s/flush_s latency split, the deprecated
tick/take shims, and the threaded continuous-batching scheduler --
deadline-triggered partial-tile launches, priority ordering under
contention, admission-control shedding, linger-based starvation avoidance,
and bitwise parity with the synchronous front-end on ragged mixed-app
traces over both backends.

Every blocking call carries an explicit timeout: a scheduler bug must fail
the test, not hang the suite (CI adds pytest-timeout as a second belt).
"""

import time
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import applications as apps
from repro.core import sobel_grid
from repro.runtime.fleet import PixieFleet
from repro.serve import (
    AdmissionError, FleetFrontend, JobHandle, StreamingFrontend,
)

WAIT = 120.0       # generous per-call bound; loaded CI hosts compile slowly
MIX = ["sobel_x", "sobel_y", "sharpen", "laplace", "threshold", "identity"]


def ragged_trace(rng, n=6, sizes=((6, 9), (11, 5), (3, 8), (8, 8))):
    return [
        (MIX[i % len(MIX)],
         rng.integers(0, 256, sizes[i % len(sizes)]).astype(np.int32))
        for i in range(n)
    ]


# -- futures API on the synchronous front-end ---------------------------------


def test_handle_result_drives_sync_flush(rng):
    img = rng.integers(0, 256, (4, 6)).astype(np.int32)
    svc = FleetFrontend(fleet=PixieFleet(default_grid=sobel_grid()))
    h = svc.submit("laplace", img)
    assert isinstance(h, JobHandle) and not h.done()
    np.testing.assert_array_equal(
        h.result(timeout=WAIT), apps.conv2d_reference(img, apps.LAPLACE)
    )
    assert h.done()
    # repeat reads are free and identical (a future, not a one-shot take)
    np.testing.assert_array_equal(h.result(), h.result())


def test_sync_latency_split_queue_vs_flush(rng):
    """The PR 6 bugfix: per-job latency separates queue wait (submit ->
    flush start) from flush duration, instead of stamping one shared
    post-flush 'now' that conflated the two for every job in the batch."""
    img = rng.integers(0, 256, (4, 6)).astype(np.int32)
    svc = FleetFrontend(fleet=PixieFleet(default_grid=sobel_grid()))
    h1 = svc.submit("sobel_x", img)
    time.sleep(0.05)
    h2 = svc.submit("sobel_y", img)
    jobs = {j.ticket: j for j in svc.flush()}
    j1, j2 = jobs[h1.ticket], jobs[h2.ticket]
    # same flush serves both: identical flush_s, differing queue_s
    assert j1.flush_s == j2.flush_s > 0
    assert j1.queue_s >= j2.queue_s + 0.04
    for j in (j1, j2):
        assert j.latency_s == pytest.approx(j.queue_s + j.flush_s)
    s = svc.latency.summary()
    assert s["completed"] == 2 and s["deadline_misses"] == 0
    assert s["queue_s"]["max"] >= 0.04


def test_process_batch_on_handles_single_dispatch(rng):
    img = rng.integers(0, 256, (8, 8)).astype(np.int32)
    svc = FleetFrontend(fleet=PixieFleet(default_grid=sobel_grid()))
    names = ["sobel_y", "identity", "sobel_x"]
    outs = svc.process_batch([(n, img) for n in names])
    assert svc.stats.dispatches == 1        # one dispatch drained them all
    for n, y in zip(names, outs):
        np.testing.assert_array_equal(y, svc.process(n, img))


def test_tick_take_shims_warn_and_match(rng):
    img = rng.integers(0, 256, (4, 6)).astype(np.int32)
    svc = FleetFrontend(fleet=PixieFleet(default_grid=sobel_grid()))
    h = svc.submit("laplace", img)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        jobs = svc.tick()
        y = svc.take(h)                     # accepts handle or bare ticket
    assert {x.category for x in w} == {DeprecationWarning}
    assert [j.ticket for j in jobs] == [h.ticket]
    np.testing.assert_array_equal(y, h.result(timeout=WAIT))


def test_sync_submit_rejects_streaming_options(rng):
    img = rng.integers(0, 256, (4, 6)).astype(np.int32)
    svc = FleetFrontend(fleet=PixieFleet(default_grid=sobel_grid()))
    with pytest.raises(TypeError, match="streaming front-end"):
        svc.submit("laplace", img, deadline_s=0.1)


# -- streaming scheduler ------------------------------------------------------


def _warmed(svc, img) -> StreamingFrontend:
    """Compile the fused overlay once so scheduler-timing tests measure
    flushes, not jit."""
    svc.process("sobel_x", img)
    svc.latency.reset()
    return svc


def test_streaming_deadline_triggers_partial_tile(rng):
    """3 requests against a tile of 8 with a tight SLO and a huge linger:
    only the deadline trigger can launch, and it must launch a PARTIAL
    tile rather than wait for 5 more requests that never come."""
    img = rng.integers(0, 256, (8, 8)).astype(np.int32)
    fleet = PixieFleet(default_grid=sobel_grid(), batch_tile=8)
    with StreamingFrontend(fleet=fleet, max_linger_s=30.0) as svc:
        _warmed(svc, img)
        partial0 = fleet.stats.partial_tile_dispatches
        t0 = time.perf_counter()
        hs = [svc.submit(n, img, deadline_s=0.25)
              for n in ["sobel_x", "sobel_y", "sharpen"]]
        jobs = [h.job(timeout=WAIT) for h in hs]
        waited = time.perf_counter() - t0
    assert fleet.stats.partial_tile_dispatches > partial0
    assert waited < 5.0                       # nowhere near the 30 s linger
    for h, j in zip(hs, jobs):
        np.testing.assert_array_equal(
            np.asarray(j.output), np.asarray(h.result())
        )
    assert {j.deadline_s for j in jobs} == {0.25}


def test_streaming_priority_under_contention(rng):
    """Queue 4 requests against a stopped worker (deterministic
    contention); on start, the high-priority pair must ride the first
    flush and the low-priority pair the second."""
    img = rng.integers(0, 256, (8, 8)).astype(np.int32)
    svc = StreamingFrontend(
        fleet=PixieFleet(default_grid=sobel_grid()),
        target_batch=2, autostart=False,
    )
    low = [svc.submit(n, img, priority=0) for n in ["sobel_x", "sobel_y"]]
    high = [svc.submit(n, img, priority=5) for n in ["sharpen", "laplace"]]
    svc.start()
    jobs_high = [h.job(timeout=WAIT) for h in high]
    jobs_low = [h.job(timeout=WAIT) for h in low]
    svc.close(timeout=WAIT)
    assert {j.flush_seq for j in jobs_high} == {0}
    assert {j.flush_seq for j in jobs_low} == {1}
    for j in jobs_high:
        assert j.priority == 5


def test_streaming_admission_control_sheds(rng):
    img = rng.integers(0, 256, (8, 8)).astype(np.int32)
    svc = StreamingFrontend(
        fleet=PixieFleet(default_grid=sobel_grid()),
        max_queue=2, autostart=False,
    )
    hs = [svc.submit("sobel_x", img) for _ in range(2)]
    with pytest.raises(AdmissionError, match="max_queue=2"):
        svc.submit("sobel_y", img)
    assert svc.latency.shed == 1
    svc.start()
    for h in hs:                              # accepted work still served
        assert h.result(timeout=WAIT).shape == img.shape
    svc.close(timeout=WAIT)
    assert svc.latency.summary()["shed"] == 1


def test_handle_result_timeout_semantics(rng):
    img = rng.integers(0, 256, (8, 8)).astype(np.int32)
    svc = StreamingFrontend(
        fleet=PixieFleet(default_grid=sobel_grid()), autostart=False,
    )
    h = svc.submit("sobel_x", img)
    assert not h.done()
    with pytest.raises(TimeoutError, match="sobel_x"):
        h.result(timeout=0.05)                # worker stopped: must expire
    svc.start()
    assert h.result(timeout=WAIT).shape == img.shape
    assert h.done()
    h.result(timeout=0)                       # done: zero timeout succeeds
    svc.close(timeout=WAIT)


def test_streaming_linger_serves_deadline_less_traffic(rng):
    """No deadline, no full tile: the linger trigger must still dispatch
    promptly instead of starving deadline-less requests."""
    img = rng.integers(0, 256, (8, 8)).astype(np.int32)
    fleet = PixieFleet(default_grid=sobel_grid(), batch_tile=8)
    with StreamingFrontend(fleet=fleet, max_linger_s=0.01) as svc:
        _warmed(svc, img)
        h = svc.submit("laplace", img)
        np.testing.assert_array_equal(
            h.result(timeout=WAIT), apps.conv2d_reference(img, apps.LAPLACE)
        )
        assert svc.latency.summary()["completed"] == 1


def test_streaming_bad_request_fails_only_its_handle(rng):
    img = rng.integers(0, 256, (8, 8)).astype(np.int32)
    with StreamingFrontend(fleet=PixieFleet(default_grid=sobel_grid())) as svc:
        with pytest.raises(KeyError, match="unknown app"):
            svc.submit("not_an_app", img)     # caller-side validation
        with pytest.raises(ValueError, match=r"\[H, W\]"):
            svc.submit("sobel_x", np.zeros((2, 3, 4)))
        with pytest.raises(ValueError, match="deadline_s"):
            svc.submit("sobel_x", img, deadline_s=0.0)
        # worker-side failure (config/grid mismatch) fails ONLY its handle
        from repro.core.grid import custom
        bad = svc.submit("sobel_x", img, grid=custom("tiny", 2, [1], 1))
        good = svc.submit("identity", img)
        with pytest.raises(Exception):
            bad.result(timeout=WAIT)
        np.testing.assert_array_equal(good.result(timeout=WAIT), img)


def test_streaming_close_drains_and_rejects(rng):
    img = rng.integers(0, 256, (8, 8)).astype(np.int32)
    svc = StreamingFrontend(fleet=PixieFleet(default_grid=sobel_grid()))
    hs = [svc.submit(n, img) for n in MIX]
    svc.close(timeout=WAIT)
    for h in hs:                              # close() drains, never drops
        assert h.done() or h.result(timeout=WAIT) is not None
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit("sobel_x", img)
    svc.close(timeout=WAIT)                   # idempotent


def test_per_bucket_flush_estimates_isolated(rng):
    """PR 7 satellite: the deadline trigger's flush-duration EWMA is
    keyed per (grid, frame-bucket) -- a slow big-frame population must
    not inflate urgency for small-frame traffic, and vice versa."""
    from repro.serve.streaming import _PendingRequest

    svc = StreamingFrontend(fleet=PixieFleet(default_grid=sobel_grid()),
                            est_flush_s=0.05, autostart=False)

    def pending(shape):
        return _PendingRequest(
            seq=0, name="sobel_x", work="sobel_x",
            image=np.zeros(shape, np.int32), grid=None, priority=0,
            t_arrival=0.0, deadline_at=None, deadline_s=None,
            handle=JobHandle(0, "sobel_x"),
        )

    small, big = pending((8, 8)), pending((256, 256))
    # same grid, different pow-2 canvas buckets -> different populations
    assert svc._flush_key(small) != svc._flush_key(big)
    # frames sharing a bucket share an estimate (17 and 30 both pad to 32)
    assert svc._flush_key(pending((17, 30))) == svc._flush_key(pending((30, 17)))
    # before any flush, both fall back to the pessimistic seed
    assert svc._estimate(small) == svc._estimate(big) == 0.05
    # teach the big population it is slow: the small one is untouched
    svc._est_flush[svc._flush_key(big)] = 0.5
    assert svc._estimate(big) == 0.5
    assert svc._estimate(small) == 0.05
    # the bench-facing scalar reports the most pessimistic population
    assert svc.est_flush_s == 0.5
    # urgency is judged per request: with 0.1 s to spare, the small
    # request has slack (est 0.05) while the big one is already urgent
    small.deadline_at = big.deadline_at = 0.1 + svc.deadline_margin_s
    assert svc._deadline_urgent([big], now=0.0)
    assert not svc._deadline_urgent([small], now=0.0)
    svc.close(timeout=WAIT)


def test_streaming_learns_estimates_per_bucket(rng):
    """Live smoke: after serving one small-frame trace, the server has a
    real EWMA entry for exactly that (grid, bucket) population."""
    svc = StreamingFrontend(fleet=PixieFleet(default_grid=sobel_grid()))
    img = rng.integers(0, 256, (8, 8)).astype(np.int32)
    hs = [svc.submit(n, img) for n in MIX]
    for h in hs:
        h.result(timeout=WAIT)
    svc.close(timeout=WAIT)
    assert len(svc._est_flush) == 1
    ((grid, Hb, Wb), est), = svc._est_flush.items()
    assert (Hb, Wb) == (16, 16) and est > 0.0   # 8 pads to the 16 floor


# -- streaming == synchronous, bitwise ----------------------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_streaming_matches_sync_ragged(backend, rng):
    """Bitwise parity on a ragged mixed-app trace: batch composition is a
    latency decision, never a values decision."""
    trace = ragged_trace(rng, n=6)
    sync = FleetFrontend(fleet=PixieFleet(default_grid=sobel_grid(),
                                          backend=backend))
    ref = sync.process_batch(trace)
    with StreamingFrontend(
        fleet=PixieFleet(default_grid=sobel_grid(), backend=backend),
        target_batch=2,                       # forces multiple partial flushes
    ) as svc:
        hs = [svc.submit(n, img, deadline_s=10.0, priority=i % 3)
              for i, (n, img) in enumerate(trace)]
        outs = [h.result(timeout=WAIT) for h in hs]
        assert svc.stats.dispatches >= 2      # genuinely continuous batching
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_streaming_matches_sync_async_ingest(backend, rng):
    """The double-buffered ingest pipeline under the streaming scheduler
    stays bitwise-equal to the sync-ingest synchronous front-end."""
    trace = ragged_trace(rng, n=4)
    ref = FleetFrontend(
        fleet=PixieFleet(default_grid=sobel_grid(), backend=backend)
    ).process_batch(trace)
    with StreamingFrontend(
        fleet=PixieFleet(default_grid=sobel_grid(), backend=backend,
                         ingest="async"),
        target_batch=2,
    ) as svc:
        outs = [svc.submit(n, img).result(timeout=WAIT) for n, img in trace]
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_streaming_matches_sync_256(backend, rng):
    """256^2 frames: the large-frame tiled path under the streaming
    scheduler (slow tier; the serving-latency CI job runs it)."""
    imgs = [rng.integers(0, 256, (256, 256)).astype(np.int32) for _ in range(3)]
    trace = list(zip(["sobel_x", "sharpen", "laplace"], imgs))
    ref = FleetFrontend(
        fleet=PixieFleet(default_grid=sobel_grid(), backend=backend)
    ).process_batch(trace)
    with StreamingFrontend(
        fleet=PixieFleet(default_grid=sobel_grid(), backend=backend),
        target_batch=2,
    ) as svc:
        outs = [svc.submit(n, i, deadline_s=60.0).result(timeout=600)
                for n, i in trace]
    for a, b in zip(ref, outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_urgent_request_preempts_staged_batch(rng):
    """An urgent-deadline request preempts a staged higher-priority batch
    mid-selection: with the worker stopped, two deadline-less
    high-priority requests stage first; a low-priority request whose
    deadline cannot survive a second flush (est_flush_s is seeded huge)
    flips to urgent and must ride the first batch instead -- counted in
    FleetStats.preempted_batches."""
    img = rng.integers(0, 256, (8, 8)).astype(np.int32)
    fleet = PixieFleet(default_grid=sobel_grid(), batch_tile=2)
    svc = StreamingFrontend(
        fleet=fleet, target_batch=2, autostart=False,
        est_flush_s=5.0,  # every pending deadline looks unservable later
        max_linger_s=0.01,
    )
    high = [svc.submit(n, img, priority=10) for n in ["sobel_x", "sharpen"]]
    urgent = svc.submit("laplace", img, priority=0, deadline_s=0.001)
    time.sleep(0.01)  # deadline expires relative to est_flush_s regardless
    svc.start()
    j_urgent = urgent.job(timeout=WAIT)
    jobs_high = [h.job(timeout=WAIT) for h in high]
    svc.close(timeout=WAIT)
    # the urgent request jumped the staged (priority-sorted) order
    assert fleet.stats.preempted_batches >= 1
    assert j_urgent.flush_seq == 0
    assert max(j.flush_seq for j in jobs_high) >= 1
    for j in jobs_high:
        assert j.output is not None
