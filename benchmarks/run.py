"""Benchmark runner: one function per paper table/figure.

  resource_table     Table I analogue (conventional vs parameterized HLO resources)
  compile_time       Sec. V-E analogue (overlay compile / map / reconfig gap)
  sobel_throughput   Sec. IV demo (four execution paths of the same Sobel)
  roofline_table     arch x shape roofline from dry-run artifacts (§Roofline)
  fleet_throughput   multi-tenant batched overlay vs sequential dispatch
  serving_latency    streaming front-end latency percentiles at offered load
  pipeline_throughput  device-resident fused chains vs staged per-stage flushes
  chaos_soak         fault-injected self-healing serving vs a fault-free oracle

Prints ``name,us_per_call,derived`` CSV rows at the end for machine
consumption, after the human-readable tables.

``--check`` additionally enforces the fleet-throughput floors (batched
dispatch and fused e2e both >= 2x), the serving-latency floors (p99
bounded at smoke load, zero deadline misses, partial tiles under deadline
pressure), and the pipeline floor (fused chain >= 1.5x the staged
per-stage oracle, merged as a ``pipeline`` block into the fleet JSON),
and writes the BENCH JSONs to the stable
``artifacts/bench/BENCH_fleet.json`` / ``artifacts/bench/BENCH_serving.json``
paths so CI runs accumulate trajectories under one artifact name each.
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

BENCH_FLEET_JSON = "artifacts/bench/BENCH_fleet.json"
BENCH_SERVING_JSON = "artifacts/bench/BENCH_serving.json"
BENCH_CHAOS_JSON = "artifacts/bench/BENCH_chaos.json"


def main(argv=None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--check", action="store_true",
                   help="enforce fleet speedup floors and write the BENCH "
                        f"JSON to {BENCH_FLEET_JSON}")
    args = p.parse_args(argv)

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()

    # Each benchmark imports INSIDE its own try block: a single broken
    # module (or a missing optional dep) must fail that one benchmark
    # loudly -- counted in `failures`, nonzero exit -- instead of an
    # import error here silently killing the whole runner before any
    # floor is checked.
    csv_rows = [("name", "us_per_call", "derived")]
    failures = []

    print("=" * 72)
    print("Benchmark 1: resource table (paper Table I analogue)")
    print("=" * 72)
    try:
        from benchmarks import resource_table

        rows = resource_table.main()
        for r in rows:
            csv_rows.append((
                f"resource/{r['component']}",
                "",
                f"total_ops_reduction={r['total_ops_reduction_pct']:.1f}%",
            ))
    except Exception as e:
        traceback.print_exc()
        failures.append(("resource_table", e))

    print()
    print("=" * 72)
    print("Benchmark 2: compilation gap (paper Sec. V-E analogue)")
    print("=" * 72)
    try:
        from benchmarks import compile_time

        rows = compile_time.main()
        for r in rows:
            csv_rows.append((f"compile/{r['stage']}", f"{r['seconds']*1e6:.1f}", ""))
    except Exception as e:
        traceback.print_exc()
        failures.append(("compile_time", e))

    print()
    print("=" * 72)
    print("Benchmark 3: Sobel execution paths (paper Sec. IV demo)")
    print("=" * 72)
    try:
        from benchmarks import sobel_throughput

        rows = sobel_throughput.main()
        for r in rows:
            csv_rows.append((
                f"sobel/{r['impl']}", f"{r['us_per_image']:.1f}",
                f"speedup={r['speedup_vs_conv']:.2f}",
            ))
    except Exception as e:
        traceback.print_exc()
        failures.append(("sobel_throughput", e))

    print()
    print("=" * 72)
    print("Benchmark 4: roofline table (arch x shape, from dry-run artifacts)")
    print("=" * 72)
    try:
        from benchmarks import roofline_table

        rows = roofline_table.main()
        for r in rows:
            if r.get("bottleneck") not in ("SKIP", "ERROR", None):
                csv_rows.append((
                    f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}",
                    f"{r['t_compute_s']*1e6 if isinstance(r.get('t_compute_s'), float) else 0:.1f}",
                    f"bottleneck={r['bottleneck']};mfu={r.get('mfu_at_roofline', 0):.4f}",
                ))
    except Exception as e:
        traceback.print_exc()
        failures.append(("roofline_table", e))

    print()
    print("=" * 72)
    print("Benchmark 5: fleet throughput (multi-tenant batched overlay)")
    print("=" * 72)
    try:
        from benchmarks import fleet_throughput

        fleet_args = ["--smoke"]
        if args.check:
            # Mirror CI's smoke-bench job: the --frames sweep adds the
            # per-size tiled/async numbers (and their floors) to the JSON.
            fleet_args += ["--check", "--frames", "--out", BENCH_FLEET_JSON]
        r = fleet_throughput.main(fleet_args)
        csv_rows.append((
            "fleet/batched_vs_sequential",
            f"{1e6 / r['batched_apps_per_s']:.1f}",
            f"speedup={r['speedup']:.2f};apps={r['n_apps']}",
        ))
        csv_rows.append((
            "fleet/fused_vs_unfused_e2e",
            f"{1e6 / r['fused_e2e_apps_per_s']:.1f}",
            f"speedup_e2e={r['speedup_e2e']:.2f};"
            f"pack_fraction={r['pack_fraction_fused']:.3f}",
        ))
    except (Exception, SystemExit) as e:
        traceback.print_exc()
        failures.append(("fleet_throughput", e))

    print()
    print("=" * 72)
    print("Benchmark 6: serving latency (streaming front-end, offered load)")
    print("=" * 72)
    try:
        from benchmarks import serving_latency

        serving_args = ["--smoke"]
        if args.check:
            serving_args += ["--check", "--out", BENCH_SERVING_JSON]
        r = serving_latency.main(serving_args)
        lat = r["loaded"]["latency"]
        csv_rows.append((
            "serving/p99_total",
            f"{1e6 * lat['total_s']['p99']:.1f}",
            f"p50={1e3*lat['total_s']['p50']:.2f}ms;"
            f"misses={lat['deadline_misses']};"
            f"partial_tiles={r['deadline']['partial_tile_dispatches']}",
        ))
    except (Exception, SystemExit) as e:
        traceback.print_exc()
        failures.append(("serving_latency", e))

    print()
    print("=" * 72)
    print("Benchmark 7: pipeline throughput (fused chains vs staged flushes)")
    print("=" * 72)
    try:
        from benchmarks import pipeline_throughput

        pipe_args = ["--smoke"]
        if args.check:
            # Runs AFTER Benchmark 5 so the 'pipeline' block merges into
            # the fleet JSON that fleet_throughput already wrote -- CI
            # uploads ONE artifact covering both.
            pipe_args += ["--check", "--out", BENCH_FLEET_JSON]
        r = pipeline_throughput.main(pipe_args)
        csv_rows.append((
            "pipeline/fused_vs_staged",
            f"{1e6 / r['fused_chains_per_s']:.1f}",
            f"speedup={r['fused_vs_staged']:.2f};depth={r['depth']};"
            f"chains={r['n_apps']}",
        ))
    except (Exception, SystemExit) as e:
        traceback.print_exc()
        failures.append(("pipeline_throughput", e))

    print()
    print("=" * 72)
    print("Benchmark 8: chaos soak (fault-injected self-healing serving)")
    print("=" * 72)
    try:
        from benchmarks import chaos_soak

        chaos_args = ["--smoke"]
        if args.check:
            chaos_args += ["--check", "--out", BENCH_CHAOS_JSON]
        r = chaos_soak.main(chaos_args)
        s = r["soak"]
        csv_rows.append((
            "chaos/availability",
            f"{1e6 * s['latency']['total_s']['p99']:.1f}",
            f"availability={s['availability_nonpoisoned']:.4f};"
            f"quarantined={s['quarantined']};hung={s['hung_handles']};"
            f"restarts={s['worker_restarts']};"
            f"breaker_recovered={s['breaker']['recovered']}",
        ))
    except (Exception, SystemExit) as e:
        traceback.print_exc()
        failures.append(("chaos_soak", e))

    print()
    print("name,us_per_call,derived")
    for name, us, derived in csv_rows[1:]:
        print(f"{name},{us},{derived}")

    if failures:
        print(f"\n{len(failures)} benchmark(s) FAILED: {[f[0] for f in failures]}")
        sys.exit(1)


if __name__ == "__main__":
    main()
