"""The ``pixie.*`` spans of the served path, read back from a profiler
trace recorded on the CPU.

Each test serves a few small frames under ``jax.profiler.trace`` and
reads the ``.xplane.pb`` with ``ProfileData``: the spans a flush emits,
their nesting and order on the thread that ran it, the pooled canvas's
wait, and that a traced run serves the same bytes as an untraced one.

A process holds one profiler session at a time; ``--dist loadfile``
keeps every test of this file in one worker, one after another.
"""

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import sobel_grid
from repro.runtime.fleet import FleetRequest, PixieFleet
from repro.serve import StreamingFrontend

WAIT = 120.0
#: What one fused or chained dispatch emits, in order (``canvas_wait``
#: only when a pooled canvas's earlier ship is still pending).
DISPATCH = ["pixie.bank", "pixie.canvas_wait", "pixie.embed", "pixie.ship",
            "pixie.execute", "pixie.unpack"]


def traced(logdir, serve):
    """Run ``serve()`` under the profiler; returns its result and the
    ``pixie.*`` spans of the trace as ``(thread, name, start, end,
    args)``, ``thread`` being the index of the host line that holds the
    span and ``args`` the dict of the arguments it was annotated with."""
    with jax.profiler.trace(str(logdir)):
        result = serve()
    path, = glob.glob(os.path.join(str(logdir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for index, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("pixie."):
                    spans.append((index, ev.name, ev.start_ns, ev.end_ns,
                                  dict(ev.stats)))
    spans.sort(key=lambda s: (s[2], -s[3]))
    return result, spans


def names(spans):
    return [s[1] for s in spans]


def dispatches(spans):
    """The fleet's spans split into dispatches: each run of spans that
    ends with ``pixie.unpack``, intake spans left out."""
    out, current = [], []
    for s in spans:
        if s[1] in ("pixie.intake", "pixie.flush", "pixie.wait_arrivals"):
            continue
        current.append(s)
        if s[1] == "pixie.unpack":
            out.append(current)
            current = []
    assert not current, names(current)
    return out


def assert_in_order(spans):
    """Each span ends before the next one starts."""
    for a, b in zip(spans, spans[1:]):
        assert a[3] <= b[2], (a, b)


def frames(rng, n, hw=(12, 16)):
    return [rng.integers(0, 256, hw).astype(np.int32) for _ in range(n)]


@pytest.mark.parametrize("ingest", ["async", "sync"])
def test_fleet_dispatch_spans_in_order_on_one_thread(tmp_path, rng, ingest):
    fleet = PixieFleet(default_grid=sobel_grid(), batch_tile=2, ingest=ingest)
    imgs = frames(rng, 2)
    reqs = [FleetRequest(app=a, image=im)
            for a, im in zip(["sobel_x", "sharpen"], imgs)]
    fleet.run_many(reqs)   # compile outside the trace

    def serve():
        return [np.asarray(y) for _ in range(3) for y in fleet.run_many(reqs)]

    _, spans = traced(tmp_path, serve)
    assert len({s[0] for s in spans}) == 1
    assert names(spans).count("pixie.intake") == 3 * len(reqs)
    groups = dispatches(spans)
    assert len(groups) == 3 == fleet.stats.dispatches - 1
    for group in groups:
        assert [n for n in DISPATCH if n in names(group)] == names(group)
        assert {"pixie.bank", "pixie.embed", "pixie.ship", "pixie.execute",
                "pixie.unpack"} <= set(names(group))
        assert_in_order(group)


@pytest.mark.parametrize("ingest", ["async", "sync"])
def test_canvas_wait_only_on_pooled_canvas_reuse(tmp_path, rng, ingest):
    """Async ingest keeps two canvases per shape: the first two flushes
    allocate, every later one reuses the canvas two flushes back and
    waits for its ship.  Sync ingest reads its outputs before the next
    flush, so nothing is ever pending."""
    fleet = PixieFleet(default_grid=sobel_grid(), batch_tile=2, ingest=ingest)
    reqs = [FleetRequest(app="sobel_x", image=im) for im in frames(rng, 2)]

    def serve():
        return [np.asarray(y) for _ in range(4) for y in fleet.run_many(reqs)]

    _, spans = traced(tmp_path, serve)
    waited = ["pixie.canvas_wait" in names(g) for g in dispatches(spans)]
    if ingest == "async":
        assert waited == [False, False, True, True]
        assert fleet.stats.canvas_pool_hits == 2
    else:
        assert waited == [False] * 4


def test_fused_and_pipeline_flush_both_execute(tmp_path, rng):
    """A flush that mixes a single-stage app with a chain makes two
    dispatches, each with its own execute and unpack spans."""
    fleet = PixieFleet(default_grid=sobel_grid(), batch_tile=2,
                       ingest="async")
    img, = frames(rng, 1)
    reqs = [FleetRequest(pipeline=["sobel_x", "threshold"], image=img),
            FleetRequest(app="sobel_y", image=img)]
    fleet.run_many(reqs)

    def serve():
        return [np.asarray(y) for y in fleet.run_many(reqs)]

    _, spans = traced(tmp_path, serve)
    groups = dispatches(spans)
    assert len(groups) == 2
    assert all(names(g).count("pixie.execute") == 1 for g in groups)
    assert fleet.stats.pipeline_dispatches == 2
    assert fleet.stats.fused_dispatches == 4


def test_channel_dispatch_executes_and_unpacks(tmp_path, rng):
    """Named-channel requests take the packed dispatch: no canvas, but the
    same bank, execute and unpack spans."""
    fleet = PixieFleet(default_grid=sobel_grid(), batch_tile=2)
    req = FleetRequest(app="threshold",
                       inputs={"p11": rng.integers(0, 256, (300,))
                               .astype(np.int32)})
    fleet.run_many([req])

    def serve():
        return fleet.run_many([req])

    _, spans = traced(tmp_path, serve)
    assert names(spans) == ["pixie.intake", "pixie.bank", "pixie.execute",
                            "pixie.unpack"]
    assert_in_order(spans)


def test_execute_spans_carry_the_kernel_steps(tmp_path, rng):
    """A pallas dispatch's execute span carries its megakernel's grid
    steps and the live ones, and the fleet's counters add up the same:
    frames in 1080p's proportions (18 x 32) on 32 x 32 canvases in row
    tiles of 8, so a frame's fourth tile and the padded app slots are
    dead.  The fused dispatch holds 3 frames in 4 slots (9 live of 16
    steps), the chain 1 (3 live of 16)."""
    fleet = PixieFleet(default_grid=sobel_grid(), batch_tile=4,
                       backend="pallas", ingest="async", tile_rows=8)
    imgs = frames(rng, 3, hw=(18, 32))
    reqs = [FleetRequest(app=a, image=im)
            for a, im in zip(["sobel_x", "sobel_y", "laplace"], imgs)]
    reqs.append(FleetRequest(pipeline=["sobel_x", "threshold"],
                             image=imgs[0]))
    fleet.run_many(reqs)   # compile outside the trace
    assert (fleet.stats.kernel_steps, fleet.stats.kernel_steps_live) == (
        32, 12)

    _, spans = traced(tmp_path, lambda: [np.asarray(y)
                                         for y in fleet.run_many(reqs)])
    executes = [s[4] for s in spans if s[1] == "pixie.execute"]
    assert sorted((a["steps"], a["live"]) for a in executes) == [
        (16, 3), (16, 9)]
    assert (fleet.stats.kernel_steps, fleet.stats.kernel_steps_live) == (
        64, 24)


def serve_stream(images, apps):
    fleet = PixieFleet(default_grid=sobel_grid(), batch_tile=2,
                       ingest="async")
    with StreamingFrontend(fleet=fleet, max_linger_s=0.05) as svc:
        handles = [svc.submit(a, im) for a, im in zip(apps, images)]
        return [np.asarray(h.result(timeout=WAIT)) for h in handles]


def test_streaming_flush_holds_its_steps(tmp_path, rng):
    """Every ``pixie.flush`` of the front end holds its requests' intake
    and one dispatch's spans, in order, on the worker's thread; the
    worker's waits for arrivals lie outside the flushes."""
    imgs = frames(rng, 6)
    apps = ["sobel_x", "sobel_y", "laplace", "sharpen", "threshold",
            "sobel_x"]
    serve_stream(imgs[:2], apps[:2])   # compile outside the trace

    _, spans = traced(tmp_path, lambda: serve_stream(imgs, apps))
    flushes = [s for s in spans if s[1] == "pixie.flush"]
    assert flushes
    worker = flushes[0][0]
    assert all(s[0] == worker for s in spans)
    inside = 0
    for flush in flushes:
        steps = [s for s in spans if s is not flush
                 and flush[2] <= s[2] and s[3] <= flush[3]]
        assert "pixie.wait_arrivals" not in names(steps)
        intake = [s for s in steps if s[1] == "pixie.intake"]
        rest = [s for s in steps if s[1] != "pixie.intake"]
        assert intake and max(s[3] for s in intake) <= rest[0][2]
        assert [n for n in DISPATCH if n in names(rest)] == names(rest)
        assert_in_order(rest)
        inside += len(steps)
    waits = [s for s in spans if s[1] == "pixie.wait_arrivals"]
    assert waits
    assert inside + len(flushes) + len(waits) == len(spans)


def test_tracing_leaves_outputs_bitwise_unchanged(tmp_path, rng):
    imgs = frames(rng, 5, hw=(10, 14))
    apps = ["sobel_x", "sharpen", "laplace", "threshold", "sobel_y"]
    plain = serve_stream(imgs, apps)
    got, spans = traced(tmp_path, lambda: serve_stream(imgs, apps))
    assert "pixie.flush" in names(spans)
    for a, b in zip(plain, got):
        np.testing.assert_array_equal(a, b)
        assert a.dtype == b.dtype
