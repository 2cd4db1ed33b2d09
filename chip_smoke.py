#!/usr/bin/env python3
"""Bring-up smoke test: the served Pixie path, compiled, on a TPU.

    python3 chip_smoke.py              # one chip (the default phase)
    python3 chip_smoke.py --chips 4    # the 2x2 mesh phase on four chips

Default phase: a ``StreamingFrontend(backend="pallas")`` on the paper's
Sobel grid serves 1920x1080 frames (values 0-255 in the grid dtype, made
from ``--seed``) spread over four library apps and the depth-3 chain
``gauss3 -> sobel_x -> threshold``, once with ``ingest="sync"`` and once
with ``ingest="async"``.  gauss3 needs 19 memory inputs and the Sobel
grid has 18, so the chain requests name the grid the library generates
for gauss3 (``for_dfg(gauss3, shape="rect")``), which hosts all three
stages.  Then a pallas ``PixieFleet`` serves small ragged frames and
chains, one flush per canvas the fleet buckets them into (16x32 up to
256x512), so the lane padding and every block width of the kernels'
layout run.  Every output is compared bit for bit with the same
requests served by a ``backend="xla"`` fleet in this process, on the
same chip.  The run fails unless every dispatch ran a pallas plan
compiled for the chip (``default_interpret()`` is False), no dispatch
fell back to another plan, no request was quarantined and no circuit
breaker moved.

``--chips 4`` runs only the mesh path and what it is compared with: a
``PixieFleet(mesh=MeshSpec(app=2, rows=2), backend="pallas")`` on
3840x2160 frames, single-stage apps and the chain (the row-banded
single-stage and chain executors), whose output must span the four
devices, checked bit for bit against the same requests on one chip
without a mesh.

Everything runs in this one process (a chip belongs to one process).
Earlier lines report the devices, the compile seconds of each phase, the
plan keys dispatched and the fleet counters; the last line of standard
output is one JSON object ``{"ok": true, "device": {...}}``, printed
only when every check passed.  Without a TPU, or without the ``repro``
package next to this file, the script exits non-zero and prints no
result.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

APPS = ("sobel_x", "sobel_y", "laplace", "threshold")
CHAIN = ("gauss3", "sobel_x", "threshold")
RESULT_TIMEOUT_S = 900.0
#: Small frames, one flush per canvas the fleet buckets them into (min
#: side 16, powers of two): 16x32, 64x128, 128x256 and 256x512.
SMALL_FLUSHES = (
    ((5, 30), (16, 9)),
    ((37, 90), (20, 70)),
    ((100, 200), (57, 250)),
    ((200, 300), (150, 480)),
)


class CompileClock:
    """Sums JAX's compile-phase durations (tracing, lowering, backend
    compile) reported through ``jax.monitoring``; ``lap()`` returns the
    seconds since the previous lap."""

    def __init__(self, jax):
        self.total = 0.0
        self._mark = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.total += duration

    def lap(self) -> float:
        seconds, self._mark = self.total - self._mark, self.total
        return seconds


def frames(seed: int, n: int, height: int, width: int, dtype):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, (height, width)).astype(dtype)
            for _ in range(n)]


def workload(seed: int, n: int, height: int, width: int, dtype):
    """``n`` requests: every fifth is the depth-3 chain, the rest cycle
    through APPS."""
    work = [list(CHAIN) if k % 5 == 4 else APPS[k % len(APPS)]
            for k in range(n)]
    return list(zip(work, frames(seed, n, height, width, dtype)))


def chain_grid():
    """The library's generated grid for gauss3, the widest chain stage."""
    from repro.core import applications, for_dfg

    return for_dfg(applications.ALL_APPS["gauss3"](), shape="rect")


def to_request(work, image):
    from repro.runtime.fleet import FleetRequest

    if isinstance(work, list):
        return FleetRequest(pipeline=work, image=image, grid=chain_grid())
    return FleetRequest(app=work, image=image)


def serve_oracle(requests, batch_tile: int):
    """The same requests through an XLA fleet without a mesh, a few at a
    time (the XLA fused executor's temporaries grow with the batch)."""
    from repro.runtime.fleet import PixieFleet

    fleet = PixieFleet(backend="xla", batch_tile=batch_tile)
    outs = []
    for k in range(0, len(requests), batch_tile):
        chunk = requests[k:k + batch_tile]
        outs += fleet.run_many([to_request(w, im) for w, im in chunk])
    return [np.asarray(o) for o in outs]


def check_stats(label: str, stats, n_requests: int) -> None:
    """The fleet counters a bring-up run must show: every dispatch ran a
    pallas plan, nothing fell back, nothing was quarantined, no breaker
    moved."""
    print(f"[{label}] plan keys dispatched:")
    for key, count in sorted(stats.dispatch_plans.items()):
        print(f"    {count:3d} x {key}")
    print(f"[{label}] executed={stats.executed} dispatches={stats.dispatches} "
          f"fallback_dispatches={stats.fallback_dispatches} "
          f"quarantined_requests={stats.quarantined_requests} "
          f"retries={stats.retries} breaker_events={stats.breaker_events} "
          f"mesh_granted={stats.mesh_granted} "
          f"mesh_degraded={stats.mesh_degraded} "
          f"output_devices={stats.output_devices}")
    problems = []
    if stats.executed != n_requests:
        problems.append(f"executed {stats.executed} of {n_requests}")
    if stats.fallback_dispatches:
        problems.append(f"{stats.fallback_dispatches} fallback dispatches")
    if stats.quarantined_requests:
        problems.append(f"{stats.quarantined_requests} quarantined requests")
    if stats.breaker_events:
        problems.append(f"breaker events {stats.breaker_events}")
    foreign = [k for k in stats.dispatch_plans if "|pallas|" not in k]
    if not stats.dispatch_plans or foreign:
        problems.append(f"non-pallas plans dispatched: {foreign}")
    if problems:
        raise AssertionError(f"[{label}] " + "; ".join(problems))


def compare(label: str, got, want, reference: str = "the XLA fleet") -> None:
    bad = [k for k, (g, w) in enumerate(zip(got, want))
           if g.dtype != w.dtype or g.shape != w.shape
           or not np.array_equal(g, w)]
    if len(got) != len(want) or bad:
        raise AssertionError(
            f"[{label}] {len(bad)} of {len(want)} outputs differ from "
            f"{reference} (first: request {bad[:1]})")
    print(f"[{label}] bitwise parity with {reference}: "
          f"{len(got)}/{len(want)} outputs")


def small_frames_phase(clock, *, seed: int) -> None:
    """Small ragged frames, each flush with a single-stage app and a
    chain: pallas fleet vs XLA fleet."""
    from repro.core import sobel_grid
    from repro.runtime.fleet import PixieFleet

    dtype = sobel_grid().dtype
    rng = np.random.default_rng(seed)
    pallas, xla = PixieFleet(backend="pallas"), PixieFleet(backend="xla")
    label = "pallas small frames"
    t0 = time.perf_counter()
    n = 0
    for k, (app_hw, chain_hw) in enumerate(SMALL_FLUSHES):
        requests = [(APPS[k % len(APPS)],
                     rng.integers(0, 256, app_hw).astype(dtype)),
                    (list(CHAIN), rng.integers(0, 256, chain_hw).astype(dtype))]
        got = [np.asarray(o) for o in
               pallas.run_many([to_request(w, im) for w, im in requests])]
        want = [np.asarray(o) for o in
                xla.run_many([to_request(w, im) for w, im in requests])]
        compare(f"{label} {app_hw} + chain {chain_hw}", got, want)
        n += len(requests)
    print(f"[{label}] served {n} requests in {time.perf_counter() - t0:.1f} "
          f"s, compile {clock.lap():.1f} s")
    check_stats(label, pallas.stats, n)


def single_chip_phase(clock, *, seed: int, n_requests: int, height: int,
                      width: int, oracle_tile: int) -> None:
    """Streaming pallas serving under both ingest modes vs the XLA fleet,
    then the small-frame flushes."""
    from repro.core import sobel_grid
    from repro.serve import StreamingFrontend

    requests = workload(seed, n_requests, height, width, sobel_grid().dtype)
    n_chain = sum(isinstance(w, list) for w, _ in requests)
    print(f"[workload] {n_requests} requests of {width}x{height} frames: "
          f"{n_requests - n_chain} over {', '.join(APPS)}, {n_chain} chains "
          f"{'->'.join(CHAIN)}")
    clock.lap()
    t0 = time.perf_counter()
    want = serve_oracle(requests, oracle_tile)
    print(f"[xla oracle] served in {time.perf_counter() - t0:.1f} s, "
          f"compile {clock.lap():.1f} s")
    for ingest in ("sync", "async"):
        label = f"pallas {ingest}"
        t0 = time.perf_counter()
        with StreamingFrontend(backend="pallas", ingest=ingest) as svc:
            handles = [
                svc.submit(w, im, chain_grid()) if isinstance(w, list)
                else svc.submit(w, im)
                for w, im in requests
            ]
            got = [np.asarray(h.result(timeout=RESULT_TIMEOUT_S))
                   for h in handles]
            stats = svc.stats
        print(f"[{label}] served {len(got)} requests in "
              f"{time.perf_counter() - t0:.1f} s, compile {clock.lap():.1f} s")
        check_stats(label, stats, n_requests)
        compare(label, got, want)
    small_frames_phase(clock, seed=seed + 1)


def mesh_phase(clock, *, seed: int, n_requests: int, n_chains: int,
               height: int, width: int) -> None:
    """The 2x2 (app, rows) mesh fleet vs the same requests on one chip:
    ``n_requests`` single-stage requests and ``n_chains`` chains."""
    from repro.core import MeshSpec, sobel_grid
    from repro.runtime.fleet import PixieFleet

    images = frames(seed, n_requests + n_chains, height, width,
                    sobel_grid().dtype)
    requests = [(APPS[k % len(APPS)] if k < n_requests else list(CHAIN), im)
                for k, im in enumerate(images)]
    print(f"[workload] {n_requests + n_chains} requests of {width}x{height} "
          f"frames: {n_requests} over {', '.join(APPS)}, {n_chains} chains "
          f"{'->'.join(CHAIN)}")
    fleet = PixieFleet(backend="pallas", mesh=MeshSpec(app=2, rows=2),
                       ingest="async", batch_tile=2)
    if fleet.stats.mesh_degraded or fleet.stats.mesh_granted != (2, 2):
        raise AssertionError(
            f"mesh not granted: {fleet.stats.mesh_granted} "
            f"(degraded={fleet.stats.mesh_degraded})")
    label = "pallas mesh 2x2"
    got = None
    for flush in range(2):
        outs = []
        # Single-stage requests and chains flush apart, so the output
        # placement of each executor is read.
        for part in (requests[:n_requests], requests[n_requests:]):
            t0 = time.perf_counter()
            outs += [np.asarray(o) for o in
                     fleet.run_many([to_request(w, im) for w, im in part])]
            print(f"[{label}] flush {flush}: {len(part)} requests in "
                  f"{time.perf_counter() - t0:.1f} s, compile "
                  f"{clock.lap():.1f} s, output over "
                  f"{fleet.stats.output_devices} devices")
            if fleet.stats.output_devices != 4:
                raise AssertionError(
                    f"mesh output spans {fleet.stats.output_devices} "
                    f"devices, not 4")
        if got is not None:
            compare(f"{label} flush {flush}", outs, got, "flush 0")
        got = outs
    check_stats(label, fleet.stats, 2 * len(requests))
    t0 = time.perf_counter()
    want = serve_oracle(requests, 1)
    print(f"[xla oracle, one chip] served in {time.perf_counter() - t0:.1f} s, "
          f"compile {clock.lap():.1f} s")
    compare(label, got, want)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: streaming serving on one chip (default); "
                        "4: the 2x2 mesh phase only")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    try:
        from repro.compile_cache import enable_compile_cache
        from repro.kernels.vcgra import default_interpret
    except ImportError as exc:
        print(f"chip_smoke: the repro package is not next to this script "
              f"({exc})", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    import jax

    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    print(f"[devices] {devices}")
    print(f"[devices] platform={platform} device_kind={kind} "
          f"count={len(devices)}")
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {platform!r})",
              file=sys.stderr)
        return 1
    if args.chips > len(devices):
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    interpret = default_interpret()
    print(f"[kernels] default_interpret()={interpret} compile cache={cache_dir}")
    if interpret:
        print("chip_smoke: Pallas would run interpreted on this TPU",
              file=sys.stderr)
        return 1
    clock = CompileClock(jax)
    try:
        if args.chips == 4:
            mesh_phase(clock, seed=args.seed, n_requests=4, n_chains=2,
                       height=2160, width=3840)
        else:
            single_chip_phase(clock, seed=args.seed, n_requests=40,
                              height=1080, width=1920, oracle_tile=4)
    except Exception:  # noqa: BLE001 -- reported, then the run fails
        traceback.print_exc()
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": args.chips}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
