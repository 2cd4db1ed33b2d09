#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest offered rate the server
sustains without a growing backlog.

    python3 perfbench/sweep.py --workload <cell> --rates 20,40,80 --seconds 8 --seed <n>

One process sets the cell up once, then offers each rate for a window of
its own.  For each rate it prints the requests offered and served, the
latency median and 95th percentile from when each request was due, and
the backlog trend: the median latency of the last fifth of the window's
requests over that of the first fifth.  A rate is sustained while that
trend stays near 1 and every request is answered.  The benchmark's cells
store a rate from such a sweep in their traffic file; they never search.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

import run


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)

    root = Path.cwd()
    spec = run.load_cell(root, args.workload)
    sys.path.insert(0, str(root / "src"))
    from repro.compile_cache import enable_compile_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    enable_compile_cache()
    import jax

    from repro.serve import StreamingFrontend

    run.find_chips(jax, int(spec["cell"]["chips"]))
    config, traffic = spec["config"], spec["traffic"]
    fleet = run.build_fleet(config)
    pool = run.traffic_gen.frame_pool(traffic["frames"], args.seed,
                                      np.dtype(fleet.default_grid.dtype))
    works = run.works_of(config)
    stream = run.traffic_gen.RequestStream(traffic, len(works), len(pool),
                                           args.seed)
    run.warm(fleet, works, pool)
    rows = []
    with StreamingFrontend(fleet=fleet) as svc:
        for rate in (float(r) for r in args.rates.split(",")):
            def submit(k):
                frame, app = stream[k]
                return svc.submit(works[app], pool[frame])

            load = run.loadgen.Load(submit)
            times = run.traffic_gen.arrival_times(rate, args.seconds,
                                                  args.seed)
            t0 = time.perf_counter() + 0.05
            run.loadgen.open_loop(load, times, t0, args.seconds,
                                  run.COLLECTORS)
            recs = sorted(load.records.values(), key=lambda r: r.k)
            lat = [r.done - r.due for r in recs if r.done is not None]
            fifth = max(len(lat) // 5, 1)
            row = {"rate": rate, "offered": len(recs), "served": len(lat),
                   "p50_ms": 1e3 * float(np.percentile(lat, 50)),
                   "p95_ms": 1e3 * float(np.percentile(lat, 95)),
                   "trend": float(np.median(lat[-fifth:])
                                  / np.median(lat[:fifth]))}
            rows.append(row)
            print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
