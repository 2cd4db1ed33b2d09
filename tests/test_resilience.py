"""Self-healing serving tests (PR 10).

Covers the resilience stack end to end: the deterministic retry/backoff
policy, per-plan circuit-breaker transitions (fake clock, no sleeps), the
bitwise-safe fallback chain, fault-injected fleet dispatch (transient
retry, persistent quarantine-by-bisection, NaN/Inf output guard), the
straggler->breaker coupling, and the supervised streaming worker (crash
restart with no hung JobHandle, worker_death injection, per-request hard
timeouts, surrender after max restarts, and the close/submit race
regression).

Every blocking call carries an explicit timeout: a supervisor bug must
fail the test, not hang the suite (CI adds pytest-timeout as a second
belt).
"""

import threading
import time

import numpy as np
import pytest

from repro.core import applications as apps
from repro.core import sobel_grid
from repro.core.plan import OverlayPlan, fallback_chain
from repro.parallel.axes import MeshSpec
from repro.runtime.chaos import FaultInjector, InjectedFault
from repro.runtime.fleet import FleetRequest, PixieFleet
from repro.runtime.fault_tolerance import HeartbeatMonitor
from repro.runtime.resilience import (
    BreakerBoard, CircuitBreaker, RetryPolicy, TransientError,
)
from repro.serve import (
    DispatchError, FleetFrontend, JobTimeout, PlanBuildError,
    QuarantinedError, StreamingFrontend,
)

WAIT = 120.0       # generous per-call bound; loaded CI hosts compile slowly
BACKENDS = ["xla", "pallas"]


def _fleet(backend="xla", float_pe=False, **kw):
    return PixieFleet(default_grid=sobel_grid(float_pe=float_pe),
                      backend=backend, **kw)


def _img(rng, shape=(8, 10), float_pe=False):
    a = rng.integers(0, 256, shape)
    return a.astype(np.float32) if float_pe else a.astype(np.int32)


def _oracle(backend, images, names, float_pe=False):
    fleet = _fleet(backend, float_pe=float_pe)
    return fleet.run_many([FleetRequest(app=n, image=im)
                           for n, im in zip(names, images)])


# -- retry policy -------------------------------------------------------------


def test_backoff_schedule_is_deterministic_and_capped():
    r = RetryPolicy(max_attempts=5, backoff_base_s=0.01,
                    backoff_multiplier=2.0, backoff_max_s=0.05)
    assert r.schedule() == (0.01, 0.02, 0.04, 0.05)   # capped at max
    assert r.schedule() == r.schedule()               # pure, no jitter
    assert r.backoff_s(10) == 0.05


def test_retry_policy_transient_classification():
    r = RetryPolicy()

    class Flaky(Exception):
        transient = True

    class Fatal(Exception):
        transient = False

    assert r.should_retry(TransientError("x"))
    assert r.should_retry(Flaky())
    assert r.should_retry(InjectedFault("dispatch", transient=True))
    assert not r.should_retry(InjectedFault("dispatch", transient=False))
    assert not r.should_retry(Fatal())
    assert not r.should_retry(ValueError("deterministic"))


def test_retry_policy_validates():
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError):
        RetryPolicy(backoff_base_s=-1.0)


# -- circuit breaker (fake clock, no sleeps) ----------------------------------


def test_breaker_opens_after_consecutive_failures_and_recovers():
    t = [0.0]
    br = CircuitBreaker("plan-a", failure_threshold=3, cooldown_s=1.0,
                        clock=lambda: t[0])
    assert br.allow()
    br.record_failure()
    br.record_failure()
    assert br.state == "closed"      # below threshold
    br.record_failure()
    assert br.state == "open"
    assert not br.allow()            # still cooling down
    t[0] = 0.5
    assert not br.allow()
    t[0] = 1.0                       # cooldown elapsed: one half-open probe
    assert br.allow()
    assert br.state == "half_open"
    assert not br.allow()            # the single probe is in flight
    br.record_success()
    assert br.state == "closed"
    assert [e["event"] for e in br.events] == ["open:dispatch", "half_open",
                                               "close"]


def test_breaker_reopens_on_failed_probe():
    t = [0.0]
    br = CircuitBreaker("plan-a", failure_threshold=1, cooldown_s=1.0,
                        clock=lambda: t[0])
    br.record_failure("boom")
    assert br.state == "open"
    t[0] = 1.0
    assert br.allow()
    br.record_failure("boom")
    assert br.state == "open"        # re-opened, new cooldown window
    t[0] = 1.5
    assert not br.allow()
    events = [e["event"] for e in br.events]
    assert events == ["open:boom", "half_open", "reopen:boom"]


def test_breaker_success_resets_consecutive_count():
    br = CircuitBreaker("plan-a", failure_threshold=2)
    br.record_failure()
    br.record_success()
    br.record_failure()
    assert br.state == "closed"      # never 2 consecutive


def test_breaker_board_shares_one_event_log():
    t = [0.0]
    board = BreakerBoard(failure_threshold=1, cooldown_s=1.0,
                         clock=lambda: t[0])
    board.breaker("a").record_failure()
    board.breaker("b").record_failure()
    assert board.states() == {"a": "open", "b": "open"}
    assert not board.all_closed()
    assert [e["plan"] for e in board.events] == ["a", "b"]
    assert board.breaker("a") is board.breaker("a")


# -- fallback chain -----------------------------------------------------------


def test_fallback_chain_degrades_every_axis_in_order():
    plan = OverlayPlan(grid=sobel_grid(), batched=True, fused=True, radius=1,
                       backend="pallas", mesh=MeshSpec(app=2, rows=2),
                       tile_rows=8, ingest="async")
    chain = fallback_chain(plan)
    assert len(chain) == 4
    # step 1: backend falls to the XLA oracle, everything else kept
    assert chain[0].backend == "xla" and chain[0].mesh == plan.mesh
    # step 2: row banding dropped (app-only mesh)
    assert chain[1].mesh == MeshSpec(app=2)
    # step 3: single device
    assert chain[2].mesh == MeshSpec()
    # step 4 (most degraded): untiled single-device XLA
    last = chain[-1]
    assert (last.backend, last.mesh, last.tile_rows) == ("xla", MeshSpec(), None)
    # every step keeps the work axes that define the computed values
    assert all(c.grid == plan.grid and c.fused and c.radius == 1
               for c in chain)


def test_fallback_chain_empty_for_already_degraded_plan():
    plan = OverlayPlan(grid=sobel_grid(), batched=True, fused=True, radius=1,
                       backend="xla", mesh=MeshSpec(), tile_rows=None)
    assert fallback_chain(plan) == ()


# -- fleet: transient retry ---------------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_transient_dispatch_faults_are_retried_bitwise(rng, backend):
    imgs = [_img(rng), _img(rng, (6, 7))]
    names = ["sobel_x", "laplace"]
    oracle = _oracle(backend, imgs, names)
    faults = FaultInjector(seed=11).inject("dispatch", max_fires=2)
    fleet = _fleet(backend, faults=faults,
                   retry=RetryPolicy(backoff_base_s=1e-4))
    outs = fleet.run_many([FleetRequest(app=n, image=im)
                           for n, im in zip(names, imgs)])
    for got, want in zip(outs, oracle):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert fleet.stats.retries == 2
    assert fleet.stats.quarantined_requests == 0


def test_nontransient_fault_skips_retries_and_uses_fallback(rng):
    # A persistent pallas-plan fault: no retry burn, straight down the
    # chain to the XLA sibling, bitwise.
    img = _img(rng)
    oracle = _oracle("xla", [img], ["sobel_x"])[0]
    faults = FaultInjector(seed=0).inject(
        "dispatch", transient=False, match=("|pallas|",))
    fleet = _fleet("pallas", faults=faults)
    out = fleet.run_many([FleetRequest(app="sobel_x", image=img)])[0]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))
    assert fleet.stats.retries == 0
    assert fleet.stats.fallback_dispatches == 1


# -- fleet: quarantine by bisection -------------------------------------------


@pytest.mark.parametrize("backend", BACKENDS)
def test_poisoned_tickets_are_exactly_isolated(rng, backend):
    names = ["sobel_x", "sobel_y", "laplace", "sharpen", "identity",
             "threshold"]
    imgs = [_img(rng, (5 + i, 7)) for i in range(len(names))]
    oracle = _oracle(backend, imgs, names)
    # Tickets 1 and 4 are poisoned persistently: every plan fails any
    # batch containing them, so bisection must quarantine exactly those
    # two and serve the other four bitwise.
    faults = FaultInjector(seed=3).inject(
        "dispatch", transient=False, match=("<ticket:1>", "<ticket:4>"))
    fleet = _fleet(backend, faults=faults,
                   retry=RetryPolicy(max_attempts=1))
    tickets = [fleet.submit(FleetRequest(app=n, image=im))
               for n, im in zip(names, imgs)]
    fleet.flush()
    for i, t in enumerate(tickets):
        if i in (1, 4):
            with pytest.raises(QuarantinedError) as ei:
                fleet.result(t)
            assert ei.value.ticket == t
            assert ei.value.app == names[i]
        else:
            np.testing.assert_array_equal(
                np.asarray(fleet.result(t)), np.asarray(oracle[i]))
    assert fleet.stats.quarantined_requests == 2


def test_quarantined_error_carries_cause():
    rng = np.random.default_rng(0)
    faults = FaultInjector(seed=0).inject(
        "dispatch", transient=False, match=("<app:threshold>",),
        detail="poison pill")
    fleet = _fleet(faults=faults, retry=RetryPolicy(max_attempts=1))
    t = fleet.submit(FleetRequest(app="threshold", image=_img(rng)))
    fleet.flush()
    with pytest.raises(QuarantinedError) as ei:
        fleet.result(t)
    assert isinstance(ei.value.cause, InjectedFault)
    assert "poison pill" in str(ei.value.cause)


# -- fleet: NaN/Inf output guard ----------------------------------------------


def test_output_guard_retries_transient_nan_bitwise(rng):
    img = _img(rng, float_pe=True)
    oracle = _oracle("xla", [img], ["sobel_x"], float_pe=True)[0]
    faults = FaultInjector(seed=5).inject(
        "nan_output", max_fires=1, match=("<app:sobel_x>",))
    fleet = _fleet(float_pe=True, faults=faults,
                   retry=RetryPolicy(backoff_base_s=1e-4))
    out = fleet.run_many([FleetRequest(app="sobel_x", image=img)])[0]
    arr = np.asarray(out)
    assert np.isfinite(arr).all()
    np.testing.assert_array_equal(arr, np.asarray(oracle))
    assert fleet.stats.guard_failures == 1


def test_output_guard_quarantines_persistent_nan_and_serves_batchmate(rng):
    imgs = [_img(rng, float_pe=True), _img(rng, (6, 7), float_pe=True)]
    names = ["sobel_x", "laplace"]
    oracle = _oracle("xla", imgs, names, float_pe=True)
    faults = FaultInjector(seed=5).inject(
        "nan_output", match=("<app:laplace>",))
    fleet = _fleet(float_pe=True, faults=faults,
                   retry=RetryPolicy(max_attempts=1))
    t_ok = fleet.submit(FleetRequest(app="sobel_x", image=imgs[0]))
    t_bad = fleet.submit(FleetRequest(app="laplace", image=imgs[1]))
    fleet.flush()
    np.testing.assert_array_equal(
        np.asarray(fleet.result(t_ok)), np.asarray(oracle[0]))
    with pytest.raises(QuarantinedError):
        fleet.result(t_bad)
    assert fleet.stats.quarantined_requests == 1


# -- fleet: breaker integration -----------------------------------------------


def test_breaker_opens_then_recovers_through_fallback(rng):
    # A pallas primary that fails 3 consecutive flushes opens its
    # breaker; traffic then goes straight to the XLA fallback without
    # even offering the primary.  Once the fault burns out and the
    # cooldown (fake clock) elapses, a half-open probe closes it again.
    img = _img(rng)
    t = [0.0]
    board = BreakerBoard(failure_threshold=3, cooldown_s=10.0,
                         clock=lambda: t[0])
    faults = FaultInjector(seed=0).inject(
        "dispatch", transient=False, match=("|pallas|",), max_fires=3)
    fleet = _fleet("pallas", faults=faults, breakers=board)
    pallas_key = None
    for _ in range(3):
        fleet.run_many([FleetRequest(app="sobel_x", image=img)])
    opened = [e for e in fleet.stats.breaker_events
              if e["event"].startswith("open:")]
    assert len(opened) == 1
    pallas_key = opened[0]["plan"]
    assert "pallas" in pallas_key
    assert board.states()[pallas_key] == "open"
    assert fleet.stats.fallback_dispatches == 3

    # Open breaker: the primary is not offered (fault is exhausted, so a
    # dispatch attempt would have SUCCEEDED -- the skip proves the
    # breaker, not the fault, routed traffic).
    fleet.run_many([FleetRequest(app="sobel_x", image=img)])
    assert fleet.stats.fallback_dispatches == 4

    # Cooldown elapses: half-open probe on the primary succeeds, closes.
    t[0] = 10.0
    fleet.run_many([FleetRequest(app="sobel_x", image=img)])
    assert board.states()[pallas_key] == "closed"
    events = [e["event"] for e in fleet.stats.breaker_events
              if e["plan"] == pallas_key]
    assert events == ["open:dispatch", "half_open", "close"]
    assert fleet.stats.fallback_dispatches == 4   # primary served it


def test_open_breaker_with_no_fallback_still_serves_as_last_resort(rng):
    # A fully-degraded plan has an empty chain; even with its breaker
    # open the fleet must dispatch it rather than fail available work.
    img = _img(rng)
    oracle = _oracle("xla", [img], ["sobel_x"])[0]
    board = BreakerBoard(failure_threshold=1, cooldown_s=1e9)
    faults = FaultInjector(seed=0).inject("dispatch", max_fires=1)
    fleet = _fleet("xla", faults=faults, breakers=board,
                   retry=RetryPolicy(max_attempts=1))
    out1 = fleet.run_many([FleetRequest(app="sobel_x", image=img)])
    assert not board.all_closed()        # single failure opened it
    out2 = fleet.run_many([FleetRequest(app="sobel_x", image=img)])
    np.testing.assert_array_equal(np.asarray(out2[0]), np.asarray(oracle))
    np.testing.assert_array_equal(np.asarray(out1[0]), np.asarray(oracle))


def test_straggler_flush_counts_against_the_breaker(rng):
    # An armed fleet (heartbeat explicitly installed) converts a flagged
    # straggler flush into breaker failures for the plans it dispatched.
    img = _img(rng)
    mon = HeartbeatMonitor(window=16, factor=1.0)
    mon.durations.extend([1e-9] * 8)     # any real flush is >> 1x median
    board = BreakerBoard(failure_threshold=1, cooldown_s=1e9)
    fleet = _fleet("xla", heartbeat=mon, breakers=board)
    fleet.run_many([FleetRequest(app="sobel_x", image=img)])
    assert fleet.stats.straggler_flushes == 1
    assert any(e["event"] == "open:straggler"
               for e in fleet.stats.breaker_events)


def test_unarmed_fleet_never_trips_breakers_on_stragglers(rng):
    # Default construction (no faults/breakers/heartbeat passed) keeps
    # the straggler->breaker coupling off: a slow first flush after
    # compile must not poison plans for a plain batch user.
    img = _img(rng)
    fleet = _fleet("xla")
    fleet.heartbeat.durations.extend([1e-9] * 8)
    fleet.run_many([FleetRequest(app="sobel_x", image=img)])
    assert fleet.stats.breaker_events == []
    assert fleet.breakers.all_closed()


# -- fleet: compile-time faults -----------------------------------------------


def test_compile_fault_falls_back_and_does_not_cache_failure(rng):
    img = _img(rng)
    oracle = _oracle("xla", [img], ["sobel_x"])[0]
    faults = FaultInjector(seed=0).inject(
        "compile", transient=False, match=("|pallas|",), max_fires=1)
    fleet = _fleet("pallas", faults=faults)
    out = fleet.run_many([FleetRequest(app="sobel_x", image=img)])[0]
    np.testing.assert_array_equal(np.asarray(out), np.asarray(oracle))
    assert fleet.stats.fallback_dispatches == 1
    # The failed build was never cached: the next flush compiles the
    # pallas primary cleanly and serves from it.
    fleet.run_many([FleetRequest(app="sobel_x", image=img)])
    assert fleet.stats.fallback_dispatches == 1


# -- fleet: unarmed build failures surface -------------------------------------


def _refuse(*args, **kwargs):
    raise NotImplementedError("kernel refused by the device compiler")


@pytest.mark.parametrize("stage", ["build", "lower"])
def test_unarmed_fleet_surfaces_plan_build_failure(rng, monkeypatch, stage):
    # An unarmed pallas fleet whose primary plan cannot be built (the
    # executor builder raises) or lowered (tracing the kernel raises)
    # fails EVERY ticket of the batch with the typed error; the XLA
    # fallback never serves it.
    import repro.kernels.vcgra.ops as kernel_ops

    target = ("_batched_fused_pallas_fn" if stage == "build"
              else "vcgra_fused_batched")
    monkeypatch.setattr(kernel_ops, target, _refuse)
    fleet = _fleet("pallas")
    tickets = [fleet.submit(FleetRequest(app=n, image=_img(rng)))
               for n in ("sobel_x", "threshold")]
    fleet.flush()
    for t in tickets:
        with pytest.raises(PlanBuildError) as ei:
            fleet.result(t)
        assert isinstance(ei.value, DispatchError)
        assert isinstance(ei.value.cause, NotImplementedError)
        assert "|pallas|" in ei.value.plan_key
    assert fleet.stats.fallback_dispatches == 0
    assert fleet.stats.quarantined_requests == 0
    assert fleet.stats.dispatches == 0


class _RuntimeFault:
    """An executable that compiles for any operands but fails when run."""

    plan = OverlayPlan(grid=sobel_grid(), batched=True, fused=True,
                       backend="pallas")

    def __init__(self):
        self.lowered = 0

    def __call__(self, *args):
        raise RuntimeError("device fault")

    def lower(self, *specs):
        self.lowered += 1
        return self

    def compile(self):
        return self


def test_unarmed_runtime_failure_is_relowered_once_per_flush(rng):
    # Classifying a failure as build-or-runtime re-lowers the executable;
    # the ladder's retries, fallbacks and bisection halves reuse the
    # verdict for the same plan and operand shapes within one flush.
    fleet = _fleet("pallas")
    fn, frames = _RuntimeFault(), _img(rng)[None]
    for _ in range(3):
        with pytest.raises(RuntimeError, match="device fault"):
            fleet._execute(fn, frames)
    assert fn.lowered == 1
    with pytest.raises(RuntimeError):
        fleet._execute(fn, frames[:, :4])        # other shapes: re-lowered
    assert fn.lowered == 2
    fleet.flush()                                # a new flush forgets
    with pytest.raises(RuntimeError):
        fleet._execute(fn, frames)
    assert fn.lowered == 3


def test_unarmed_build_failure_reaches_every_handle(rng, monkeypatch):
    import repro.kernels.vcgra.ops as kernel_ops

    monkeypatch.setattr(kernel_ops, "vcgra_fused_batched", _refuse)
    svc = FleetFrontend(fleet=_fleet("pallas"))
    handles = [svc.submit(n, _img(rng)) for n in ("sobel_x", "laplace")]
    for h in handles:
        with pytest.raises(PlanBuildError):
            h.result(timeout=WAIT)
    assert svc.stats.fallback_dispatches == 0


# -- sync front-end routing ---------------------------------------------------


def test_sync_frontend_routes_quarantine_to_the_handle(rng):
    faults = FaultInjector(seed=0).inject(
        "dispatch", transient=False, match=("<app:threshold>",))
    svc = FleetFrontend(fleet=_fleet(faults=faults,
                                     retry=RetryPolicy(max_attempts=1)))
    h_ok = svc.submit("sobel_x", _img(rng))
    h_bad = svc.submit("threshold", _img(rng))
    out = h_ok.result(timeout=WAIT)      # drives the flush
    assert np.asarray(out).shape == (8, 10)
    with pytest.raises(QuarantinedError):
        h_bad.result(timeout=WAIT)
    assert svc.latency.failed == 1


# -- streaming: supervised worker ---------------------------------------------


class Boom(BaseException):
    """A worker-killing failure below Exception (like SystemExit from a
    wedged extension): only the supervisor may catch it."""


def test_streaming_worker_crash_strands_no_handle(rng):
    svc = StreamingFrontend(backend="xla", autostart=False)
    orig_flush = svc.fleet.flush
    calls = {"n": 0}

    def crashing_flush(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 1:
            raise Boom("simulated hard crash mid-dispatch")
        return orig_flush(*a, **kw)

    svc.fleet.flush = crashing_flush
    svc.start()
    h1 = svc.submit("sobel_x", _img(rng))
    with pytest.raises(DispatchError, match="crashed"):
        h1.result(timeout=WAIT)
    # The restarted worker keeps serving.
    h2 = svc.submit("sobel_x", _img(rng))
    assert np.asarray(h2.result(timeout=WAIT)).shape == (8, 10)
    assert svc.worker_restarts == 1
    assert svc.latency.failed == 1
    svc.close(timeout=WAIT)


def test_streaming_worker_death_injection_restarts_and_serves(rng):
    img = _img(rng)
    with StreamingFrontend(backend="xla") as oracle_svc:
        want = oracle_svc.submit("sobel_x", img).result(timeout=WAIT)
    faults = FaultInjector(seed=3).inject("worker_death", max_fires=1)
    with StreamingFrontend(backend="xla", faults=faults) as svc:
        out = svc.submit("sobel_x", img).result(timeout=WAIT)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
        assert svc.worker_restarts == 1
        assert faults.fired.get("worker_death") == 1


def test_streaming_supervisor_surrenders_after_max_restarts(rng):
    # max_worker_restarts=0: the first crash exceeds the budget, so the
    # supervisor surrenders -- every accepted handle fails typed (the
    # in-flight batch AND anything still pending/queued), the front-end
    # closes itself, and close() must not hang on the dead worker.
    svc = StreamingFrontend(backend="xla", autostart=False,
                            max_worker_restarts=0)

    def always_boom(*a, **kw):
        raise Boom("persistent crash")

    svc.fleet.flush = always_boom
    handles = [svc.submit("sobel_x", _img(rng)) for _ in range(3)]
    svc.start()
    for h in handles:
        with pytest.raises(DispatchError):
            h.result(timeout=WAIT)
    svc.close(timeout=WAIT)              # must not hang on a dead worker
    assert svc.worker_restarts == 1      # the crash that broke the budget
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit("sobel_x", _img(rng))


def test_streaming_quarantine_fails_only_its_handle(rng):
    img = _img(rng)
    with StreamingFrontend(backend="xla") as oracle_svc:
        want = oracle_svc.submit("sobel_x", img).result(timeout=WAIT)
    faults = FaultInjector(seed=5).inject(
        "dispatch", transient=False, match=("<app:threshold>",))
    with StreamingFrontend(backend="xla", faults=faults) as svc:
        h_ok = svc.submit("sobel_x", img)
        h_bad = svc.submit("threshold", img)
        np.testing.assert_array_equal(
            np.asarray(h_ok.result(timeout=WAIT)), np.asarray(want))
        with pytest.raises(QuarantinedError):
            h_bad.result(timeout=WAIT)
        assert svc.stats.quarantined_requests == 1
        assert svc.latency.failed == 1


def test_streaming_request_hard_timeout_expires_queued_work(rng):
    # The worker is held stopped while a request ages past its hard
    # timeout; on start the sweep must fail it with JobTimeout (which is
    # also a TimeoutError) and keep serving fresh work.
    svc = StreamingFrontend(backend="xla", autostart=False,
                            request_timeout_s=0.05)
    h = svc.submit("sobel_x", _img(rng))
    time.sleep(0.1)
    svc.start()
    with pytest.raises(JobTimeout):
        h.result(timeout=WAIT)
    assert isinstance(JobTimeout("x"), TimeoutError)
    h2 = svc.submit("sobel_x", _img(rng))
    assert np.asarray(h2.result(timeout=WAIT)).shape == (8, 10)
    assert svc.latency.failed == 1
    svc.close(timeout=WAIT)


# -- streaming: close/submit race regression ----------------------------------


def test_submit_close_race_strands_no_handle(rng):
    # Regression for the pre-PR 10 race: submit() checked _closed, then
    # enqueued -- a close() between the two could insert the _STOP
    # sentinel first and strand the late request behind it, hanging its
    # handle forever.  Both now run under one lifecycle lock, so every
    # accepted handle resolves (served before shutdown) and late submits
    # are rejected loudly.  Run several rounds to give a regressed race
    # real chances to interleave.
    img = _img(rng, (4, 6))
    for round_ in range(5):
        svc = StreamingFrontend(backend="xla", max_linger_s=1e-4)
        svc.submit("sobel_x", img).result(timeout=WAIT)   # warm compile
        accepted = []
        rejected = []
        barrier = threading.Barrier(2)

        def submitter():
            barrier.wait()
            for _ in range(50):
                try:
                    accepted.append(svc.submit("sobel_x", img))
                except RuntimeError:     # closed (AdmissionError also OK)
                    rejected.append(1)
                    break

        th = threading.Thread(target=submitter)
        th.start()
        barrier.wait()
        svc.close(timeout=WAIT)
        th.join(WAIT)
        assert not th.is_alive()
        for h in accepted:               # accepted => served, never stuck
            assert np.asarray(h.result(timeout=WAIT)).shape == img.shape
