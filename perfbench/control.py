#!/usr/bin/env python3
"""The control of a cell's comparison: what a run's check reads when a
lower precision than the configuration states stands in the program's
place.  The benchmark's own runs never run it.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3 --dtype int16 [--requests 800]
    python3 perfbench/control.py --workload <cell> --seeds 1,2,3 --program-bits 16 --seconds 5

``--dtype``: the plain reference, computed in that integer type, in the
program's place.  For each seed it answers the first ``--requests``
requests of the run's stream (by default, what an open-loop window
offers; a closed loop needs the number, as many as a run serves) and
prints the run's ``wrong_outputs`` reading for those answers (values
compared as int32).  Host numpy only.

``--program-bits``: the program with its own narrower datapath switched
on (the configuration's grid at that ``data_bits``), served through the
benchmark's whole run for ``--seconds`` on the chip; prints each run's
result line, whose ``checks`` hold the readings.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

import reference
import run
import traffic_gen


def reference_control(spec: dict, seed: int, dtype, n_requests: int) -> dict:
    """A run's ``wrong_outputs`` reading with the reference in ``dtype``
    answering the first ``n_requests`` requests."""
    traffic = spec["traffic"]
    works = run.works_of(spec["config"])
    pool = traffic_gen.frame_pool(traffic["frames"], seed, np.int32)
    stream = traffic_gen.RequestStream(traffic, len(works), len(pool), seed)
    answers = {}
    records = []
    for k in range(n_requests):
        key = stream[k]
        if key not in answers:
            frame, app = key
            answers[key] = run.loadgen.digest(
                reference.serve(works[app], pool[frame], dtype))
        records.append(run.loadgen.Record(k, 0.0, 0.0, 0.0,
                                          digest=answers[key]))
    return run.compare(records, stream, works, pool)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--dtype", choices=("int16", "int8"))
    p.add_argument("--requests", type=int)
    p.add_argument("--program-bits", type=int)
    p.add_argument("--seconds", type=float, default=5.0)
    args = p.parse_args(argv)
    root = Path.cwd()
    spec = run.load_cell(root, args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.dtype:
        n = args.requests
        if n is None:
            if spec["traffic"]["loop"] != "open":
                p.error("a closed loop needs --requests")
            bench = json.loads((root / "BENCHMARK.json").read_text())
            n = int(round(spec["traffic"]["rate_per_s"] * bench["run_seconds"]))
        for seed in seeds:
            got = reference_control(spec, seed, getattr(np, args.dtype), n)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": f"reference in {args.dtype}",
                              **got}), flush=True)
        return 0
    if args.program_bits:
        sys.path.insert(0, str(root / "src"))
        from repro.compile_cache import enable_compile_cache

        enable_compile_cache()
        import jax

        run.find_chips(jax, int(spec["cell"]["chips"]))
        spec["config"]["data_bits"] = args.program_bits
        for seed in seeds:
            result = run.run_cell(spec, seed=seed, seconds=args.seconds,
                                  trace=False, chips=int(spec["cell"]["chips"]),
                                  t_start=time.perf_counter())
            print(json.dumps({"workload": args.workload, "seed": seed,
                              "control": f"program at {args.program_bits} bits",
                              **result}), flush=True)
        return 0
    p.error("give --dtype or --program-bits")


if __name__ == "__main__":
    sys.exit(main())
