#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process finds.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell is an entry of ``workloads`` in
``BENCHMARK.json``; its configuration (``configs/<config>.json``), its
traffic mix (``traffic/<traffic>.json``) and each metric's reader
(``metrics/<stem>.py``, ``<stem>`` being the metric's name up to its
first ``.``) are found by name, so a cell, a mix or a metric is added with
files and entries alone.

In order: turn on the persistent compile cache, fail without a TPU or
with fewer chips than the cell asks for, build the fleet the
configuration states, make the frames from ``--seed``, warm every
executable the window's shapes use, serve the traffic for ``--seconds``
through ``StreamingFrontend.submit`` -> ``JobHandle.result`` -> a host
array, then compare every served output with the plain numpy reference
(``reference.py``).  With ``--trace 1`` the window runs
under the JAX profiler and the per-layer metrics are printed instead of
the end-to-end ones.

Earlier lines of standard output describe the run; its last line is one
JSON object.  The numbers compared for ``correct`` are the last lines of
standard error and the last key of that object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from concurrent.futures import ThreadPoolExecutor  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import loadgen  # noqa: E402
import reference  # noqa: E402
import trace_reduce  # noqa: E402
import traffic_gen  # noqa: E402
import workcount  # noqa: E402

#: Threads that wait for the open loop's answers, in sending order.
COLLECTORS = 8


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


# -- the cell, from data ------------------------------------------------------


def load_cell(root: Path, name: str) -> dict:
    """The cell ``name`` with its configuration, traffic and metric
    entries, read from ``BENCHMARK.json`` and the files it names."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    config_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = json.loads((root / config_entry["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{cell['traffic']}.json")
                         .read_text())

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": mine(bench["end_to_end"]),
            "per_layer": mine(bench["per_layer"])}


def reader(metric: str):
    """The reader of ``metric``: the file of its name up to the first
    ``.`` (``canvas_util_pct.lat`` and ``.tput`` share one reader; the
    suffix names the cells' end-to-end metric it moves)."""
    stem = metric.split(".")[0]
    path = HERE / "metrics" / f"{stem}.py"
    if not path.exists():
        raise FileNotFoundError(f"no reader for metric {metric!r} in metrics/")
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{stem}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def works_of(config: dict) -> List:
    """What tenants ask for: the app names, or the one chain."""
    if "chain" in config:
        return [list(config["chain"])]
    return list(config["apps"])


# -- the system under test ----------------------------------------------------


def build_fleet(config: dict):
    from repro.core import MeshSpec, applications, for_dfg, sobel_grid
    from repro.runtime.fleet import PixieFleet

    g = config["grid"]
    bits = int(config["data_bits"])
    if g["generator"] == "sobel_grid":
        grid = sobel_grid(data_bits=bits)
    elif g["generator"] == "for_dfg":
        grid = for_dfg(applications.ALL_APPS[g["app"]](), shape=g["shape"],
                       data_bits=bits)
    else:
        raise ValueError(f"unknown grid generator {g['generator']!r}")
    return PixieFleet(default_grid=grid, backend=config["backend"],
                      ingest=config["ingest"],
                      batch_tile=int(config["batch_tile"]),
                      mesh=MeshSpec(**config.get("mesh", {})))


def fleet_request(work, image):
    from repro.runtime.fleet import FleetRequest

    if isinstance(work, list):
        return FleetRequest(pipeline=work, image=image)
    return FleetRequest(app=work, image=image)


def warm(fleet, works: List, pool: List[np.ndarray]) -> int:
    """Build every executable the window's shapes use; returns flushes:
    one flush of each size 1..tile (the kernel, and the per-flush unpack
    program async ingest builds for each count of frames), and each app
    alone."""
    tile = fleet.batch_tile
    batches = [[(works[i % len(works)], pool[i % len(pool)])
                for i in range(n)] for n in range(1, tile + 1)]
    batches += [[(w, pool[0])] for w in works]
    for batch in batches:
        for out in fleet.run_many([fleet_request(w, im) for w, im in batch]):
            np.asarray(out)
    return len(batches)


class CompileClock:
    """Counts JAX's backend compiles (persistent-cache hits included) and
    sums the seconds of every compile phase, through ``jax.monitoring``."""

    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
            if event == self.BACKEND:
                self.count += 1


class GcClock:
    """Times the interpreter's full (generation 2) collections while it
    is attached: a long one stalls every thread, the client's too."""

    def __init__(self):
        self.count = 0
        self.longest_s = 0.0
        self._started = None
        gc.callbacks.append(self._on_gc)

    def _on_gc(self, phase: str, info: dict) -> None:
        if info["generation"] != 2:
            return
        if phase == "start":
            self._started = time.perf_counter()
        elif self._started is not None:
            self.count += 1
            self.longest_s = max(self.longest_s,
                                 time.perf_counter() - self._started)

    def detach(self) -> None:
        gc.callbacks.remove(self._on_gc)


# -- what a window leaves to read ----------------------------------------------


@dataclasses.dataclass
class Context:
    """Everything a metric reader may read after the window."""

    seconds: float
    t0: float
    setup_s: float
    records: List[loadgen.Record]
    hws: Dict[int, tuple]             # request -> frame (H, W)
    kinds: Dict[int, str]             # request -> "fused" | "pipeline"
    work: Dict[int, tuple]            # request -> (ops, bytes)
    service_latency: dict
    fleet_delta: dict
    window_compiles: int
    peak: Optional[dict]
    trace: Optional[dict] = None
    trace_window_s: float = 0.0

    def served(self) -> List[loadgen.Record]:
        return [r for r in self.records if r.done is not None]

    def latencies_s(self) -> List[float]:
        return [r.done - r.due for r in self.served()]

    def served_in_window(self) -> int:
        end = self.t0 + self.seconds
        return sum(1 for r in self.served() if r.done <= end)

    def served_pixels(self) -> int:
        return sum(self.hws[r.k][0] * self.hws[r.k][1] for r in self.served())

    def canvas_pixels(self) -> int:
        total = 0
        for key, count in self.fleet_delta["plans"].items():
            n, hb, wb = key.rsplit("|", 1)[1][1:].split("x")
            total += int(n) * int(hb) * int(wb) * count
        return total

    def roofline_pct(self, kind: str, match) -> Optional[float]:
        """Roofline time of the served ``kind`` requests over the device
        time of the ops ``match`` accepts.  None where the window also
        served another kind: the trace names every megakernel alike."""
        if self.trace is None or self.peak is None:
            return None
        kernel_s = trace_reduce.op_seconds(self.trace["ops"], match)
        mine = [r.k for r in self.served() if self.kinds[r.k] == kind]
        if kernel_s <= 0 or not mine or len(mine) < len(self.served()):
            return None
        least = sum(workcount.roofline_s(*self.work[k], self.peak)
                    for k in mine)
        return 100.0 * least / kernel_s


def fleet_snapshot(stats, timings) -> dict:
    return {"pack_s": timings["pack_s"], "dispatches": stats.dispatches,
            "plans": dict(stats.dispatch_plans)}


def fleet_delta(a: dict, b: dict) -> dict:
    plans = {k: v - a["plans"].get(k, 0) for k, v in b["plans"].items()}
    return {"pack_s": b["pack_s"] - a["pack_s"],
            "dispatches": b["dispatches"] - a["dispatches"],
            "plans": {k: v for k, v in plans.items() if v}}


def stats_lines(stats) -> List[str]:
    """The fleet counters a served pallas run must show (as chip_smoke.py
    checks them): pallas plans only, no fallback, no quarantine, no
    breaker event."""
    foreign = [k for k in stats.dispatch_plans if "|pallas|" not in k]
    lines = [f"[fleet] {count} x {key}"
             for key, count in sorted(stats.dispatch_plans.items())]
    lines.append(
        f"[fleet] executed={stats.executed} dispatches={stats.dispatches} "
        f"fallback_dispatches={stats.fallback_dispatches} "
        f"quarantined_requests={stats.quarantined_requests} "
        f"breaker_events={len(stats.breaker_events)} "
        f"non_pallas_plans={len(foreign)} "
        f"partial_tile_dispatches={stats.partial_tile_dispatches} "
        f"padded_app_slots={stats.padded_app_slots} "
        f"mesh_granted={stats.mesh_granted} "
        f"output_devices={stats.output_devices}")
    return lines


# -- the comparison that decides ``correct`` -----------------------------------


def compare(records, stream, works, pool) -> dict:
    """Every served answer against the reference: ``wrong_outputs``
    counts those whose dtype, shape or bytes differ."""
    served = [r for r in records if r.digest is not None]
    needed = sorted({stream[r.k] for r in served})

    def ref(key):
        frame, app = key
        return key, loadgen.digest(reference.serve(works[app], pool[frame]))

    with ThreadPoolExecutor(4) as ex:
        want = dict(ex.map(ref, needed))
    wrong = sum(r.digest != want[stream[r.k]] for r in served)
    return {"wrong_outputs": wrong, "compared": len(served)}


# -- one run --------------------------------------------------------------------


def run_cell(spec: dict, *, seed: int, seconds: float, trace: bool,
             chips: int, t_start: float, log=print) -> dict:
    """Set up, serve one window, read the metrics, compare; returns the
    result object (``checks`` last)."""
    import jax

    from repro.serve import StreamingFrontend

    config, traffic = spec["config"], spec["traffic"]
    devices = jax.devices()[:chips]
    clock = CompileClock(jax)
    marks = [("start to devices", time.perf_counter())]
    fleet = build_fleet(config)
    dtype = np.dtype(fleet.default_grid.dtype)
    pool = traffic_gen.frame_pool(traffic["frames"], seed, dtype)
    works = works_of(config)
    stream = traffic_gen.RequestStream(traffic, len(works), len(pool), seed)
    marks.append(("fleet and frames", time.perf_counter()))
    n_warm = warm(fleet, works, pool)
    marks.append(("warm-up", time.perf_counter()))
    log(f"[setup] fleet {fleet.stats.backend}/{fleet.stats.ingest} "
        f"tile={fleet.batch_tile} mesh={fleet.stats.mesh_granted}; "
        f"{len(pool)} frames; {n_warm} warm-up flushes, {clock.count} "
        f"compiles, {clock.seconds:.3f} s compiling")

    svc = StreamingFrontend(fleet=fleet)
    try:
        handles = [svc.submit(works[i % len(works)], pool[i % len(pool)])
                   for i in range(fleet.batch_tile)]
        for h in handles:
            np.asarray(h.result(timeout=loadgen.GRACE_S))
        svc.latency.reset()
        marks.append(("front end", time.perf_counter()))
        log("[setup] seconds: " + ", ".join(
            f"{name} {t - t_prev:.3f}" for (name, t), t_prev
            in zip(marks, [t_start] + [t for _, t in marks])))

        def submit(k):
            frame, app = stream[k]
            return svc.submit(works[app], pool[frame])

        load = loadgen.Load(submit)
        before = fleet_snapshot(fleet.stats, fleet.timings)
        trace_dir = None
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0   # it would slow the host path
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            t_trace = time.perf_counter()
        compiles0 = clock.count
        gc_clock = GcClock()
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        if traffic["loop"] == "open":
            times = traffic_gen.arrival_times(traffic["rate_per_s"], seconds,
                                              seed)
            loadgen.open_loop(load, times, t0, seconds, COLLECTORS)
        elif traffic["loop"] == "closed":
            loadgen.closed_loop(load, int(traffic["clients"]), t0, seconds)
        else:
            raise ValueError(f"unknown loop {traffic['loop']!r}")
        window_compiles = clock.count - compiles0
        gc_clock.detach()
        trace_window_s = 0.0
        if trace:
            trace_window_s = time.perf_counter() - t_trace
            jax.profiler.stop_trace()
        after = fleet_snapshot(fleet.stats, fleet.timings)
        service_latency = svc.latency.summary()
        memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                          for d in devices)
    finally:
        svc.close()
    for line in stats_lines(fleet.stats):
        log(line)
    records = sorted(load.records.values(), key=lambda r: r.k)
    del load, submit, svc, fleet
    gc.collect()

    hws, kinds, work = {}, {}, {}
    for r in records:
        frame, app = stream[r.k]
        w = works[app]
        hws[r.k] = pool[frame].shape
        kinds[r.k] = "pipeline" if isinstance(w, list) else "fused"
        work[r.k] = workcount.request_work(w, pool[frame].shape,
                                           dtype.itemsize)
    lateness = [r.sent - r.due for r in records]
    log(f"[client] full collections in the window: {gc_clock.count}, "
        f"longest {1e3 * gc_clock.longest_s:.3f} ms")
    if traffic["loop"] == "open" and lateness:
        log("[client] generator lateness ms: p50 %.3f p99 %.3f max %.3f" % tuple(
            1e3 * v for v in (np.percentile(lateness, 50),
                              np.percentile(lateness, 99), max(lateness))))
    kind = devices[0].device_kind
    ctx = Context(
        seconds=seconds, t0=t0, setup_s=setup_s, records=records, hws=hws,
        kinds=kinds, work=work, service_latency=service_latency,
        fleet_delta=fleet_delta(before, after),
        window_compiles=window_compiles,
        peak=workcount.peaks(kind) if trace else None)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    breakdown = None
    if trace:
        events = trace_reduce.read_xplane(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        ctx.trace = trace_reduce.reduce(events)
        ctx.trace_window_s = trace_window_s
        device["busy_s"] = ctx.trace["busy_s"]
        device["window_s"] = trace_window_s
        breakdown = {"device_ops": ctx.trace["top_ops"],
                     "idle_gaps": ctx.trace["idle_gaps"]}

    metrics = {}
    for m in spec["per_layer"] if trace else spec["end_to_end"]:
        value = reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    served = ctx.served()
    lat = ctx.latencies_s()
    if lat:
        log("[window] latency ms from due: p50 %.3f p95 %.3f max %.3f" % tuple(
            1e3 * v for v in (np.percentile(lat, 50), np.percentile(lat, 95),
                              max(lat))))
    log(f"[window] {len(records)} requests, {len(served)} served, "
        f"{ctx.served_in_window()} inside the window; "
        f"{ctx.fleet_delta['dispatches']} dispatches, "
        f"{window_compiles} compiles in the window")

    checked = compare(records, stream, works, pool)
    missing = len(records) - len(served)
    checks = {
        "wrong_outputs": {"value": checked["wrong_outputs"], "limit": 0},
        "missing_answers": {"value": missing, "limit": 0},
    }
    correct = (checked["compared"] > 0 and all(
        c["value"] <= c["limit"] for c in checks.values()))
    log(f"[check] compared all {checked['compared']} served outputs with "
        f"the numpy reference")
    result = {"correct": bool(correct), "attempted": len(records),
              "failed": missing, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def find_chips(jax, chips: int) -> None:
    from repro.kernels.vcgra import default_interpret

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoChip(f"no TPU: JAX's platform is {platform!r}")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds "
                     f"{len(devices)}")
    if default_interpret():
        raise NoChip("Pallas would run interpreted on this device")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    root = Path.cwd()
    spec = load_cell(root, args.workload)
    sys.path.insert(0, str(root / "src"))
    try:
        from repro.compile_cache import enable_compile_cache
    except ImportError as exc:
        print(f"perfbench: the system under test is not in this checkout "
              f"({exc})", file=sys.stderr)
        return 2
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_dir = enable_compile_cache()
    import jax

    # Persist every executable, the sub-second unpack programs too, so a
    # run after the first compiles nothing and set-up stays steady.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    chips = int(spec["cell"]["chips"])
    try:
        find_chips(jax, chips)
    except NoChip as exc:
        print(f"perfbench: {exc}", file=sys.stderr)
        return 1
    print(f"[devices] {jax.devices()[:chips]} compile cache {cache_dir}")
    result = run_cell(spec, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), chips=chips, t_start=T_START)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    sys.stdout.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
