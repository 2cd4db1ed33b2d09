"""Benchmark 5 — fleet throughput: multi-tenant batched overlay dispatch,
with and without fused device-side ingest.

The overlay's compile-once economics (paper Sec. V-E) amortize the FPGA
compile across applications *in time* (sequential reconfiguration); the
fleet runtime amortizes it *in space*: N different applications stacked
into one vmapped dispatch of the same executable.  Every measured path is
one cell of the `OverlayPlan` axis product, compiled by the single
entrypoint `repro.core.plan.compile_plan` (PR 1 measured that the batched
dispatch got ~2.6x faster while end-to-end serving was capped at ~1.7x by
per-request input packing -- ~20 host-issued device ops per frame; the
fused plans below are what closed that gap):

  sequential     one conventional `Pixie`, N per-app dispatches of the
                 compiled overlay (settings swap between calls)
  batched        ONE dispatch of the batched (pre-packed channels)
                 `OverlayPlan` over the N stacked configs
  unfused e2e    per-request `stencil_inputs` + `pack_inputs` + dispatch
                 (the PR 1 serving path, kept as the oracle)
  fused e2e      `PixieFleet.run_many` on raw frames -- a fused batched
                 `OverlayPlan`: pack + dispatch + unpack as ONE
                 executable per grid
  pallas e2e     the same fused fleet plan on `backend="pallas"`: the
                 batched fused-ingest megakernel (interpret mode off-TPU),
                 measured so the BENCH trajectory covers both backends

`--frames` additionally sweeps frame sizes (default 32^2/128^2/256^2) and
records, per size, the row-tiled vs untiled fused plans (`tile_rows`) and
the sync vs async double-buffered ingest pipelines (`ingest`) -- the two
PR 5 plan axes -- into a `frames` block of the BENCH JSON.

Identical inputs, bitwise-identical outputs (asserted), compile-once
invariants asserted via the fleet's cache counters.  Emits a machine-
readable ``BENCH {json}`` line (incl. the pack fraction of both e2e
paths) plus a JSON artifact for CI trend tracking (``--out``).

Usage:
  python benchmarks/fleet_throughput.py                 # full run
  python benchmarks/fleet_throughput.py --smoke         # CI-sized (<30 s)
  python benchmarks/fleet_throughput.py --frames        # + size sweep
  python benchmarks/fleet_throughput.py --check         # exit 1 on floors
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import MeshSpec, Pixie, sobel_grid
from repro.core import applications as apps
from repro.core.bitstream import VCGRAConfig
from repro.core.interpreter import pack_inputs, pad_channels
from repro.core.tiling import TILE_AUTO, hbm_read_model, resolve_tile_rows
from repro.kernels.vcgra import default_interpret
from repro.runtime.fleet import FleetRequest, PixieFleet

# Library apps that fit the paper's 18-input Sobel grid.
FLEET_APPS = ["sobel_x", "sobel_y", "sharpen", "laplace", "threshold", "identity"]

# The pallas megakernel runs in *interpret mode* on CPU CI, so it is not
# expected to beat the hand-lowered XLA path there -- the floor only guards
# against catastrophic regressions (a broken kernel, an accidental
# per-frame retrace).  Measured ~0.5x of the XLA fused path on CPU.
PALLAS_FLOOR_VS_XLA = 0.05


def _time(fn, reps: int) -> float:
    jax.block_until_ready(fn())  # warm / compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps


def run(n_apps: int, image_hw: int, reps: int) -> dict:
    rng = np.random.default_rng(0)
    grid = sobel_grid()
    img = jnp.asarray(rng.integers(0, 256, (image_hw, image_hw)).astype(np.int32))
    taps = apps.stencil_inputs(img)

    names = [FLEET_APPS[i % len(FLEET_APPS)] for i in range(n_apps)]
    fleet = PixieFleet(default_grid=grid, batch_tile=n_apps)
    configs = [fleet.config_for(n, grid) for n in names]
    xs = [
        pad_channels(pack_inputs(c, {k: v for k, v in taps.items()
                                     if k in c.input_order}, grid.dtype),
                     grid.num_inputs)
        for c in configs
    ]

    # -- sequential baseline: N per-app dispatches of the compiled overlay --
    pix = Pixie(grid, mode="conventional")
    pix.compile_overlay(batch=img.size)
    overlay = pix._overlay_fn
    cfg_jax = [c.to_jax() for c in configs]

    def sequential():
        return [overlay(cj, x) for cj, x in zip(cfg_jax, xs)]

    # -- batched fleet dispatch: ONE dispatch for all N tenants --------------
    batched_fn = fleet.overlay_for(grid)
    stacked = VCGRAConfig.stack(configs)
    xstack = jnp.stack(xs)

    def batched():
        return batched_fn(stacked, xstack)

    # bitwise-identical outputs
    seq_out = [np.asarray(y) for y in sequential()]
    bat_out = np.asarray(batched())
    for i in range(n_apps):
        np.testing.assert_array_equal(bat_out[i], seq_out[i])

    t_seq = _time(sequential, reps)
    t_bat = _time(batched, reps)

    # -- end-to-end service paths --------------------------------------------
    # unfused: the PR 1 serving cost -- per-request host-side tap formation
    # and packing (~20 device ops/frame) + one dispatch per app.
    def unfused_e2e():
        outs = []
        for c in configs:
            t = apps.stencil_inputs(img)
            feed = {k: v for k, v in t.items() if k in c.input_order}
            x = pad_channels(pack_inputs(c, feed, grid.dtype), grid.num_inputs)
            outs.append(overlay(c.to_jax(), x))
        return outs

    # fused: raw frames into the fleet; line buffers form inside the ONE
    # batched dispatch per grid.
    requests = [FleetRequest(app=n, image=img) for n in names]

    def fused_e2e():
        return fleet.run_many(requests)

    # fused outputs == unfused outputs, bitwise
    fused_out = fused_e2e()
    for i in range(n_apps):
        np.testing.assert_array_equal(
            np.asarray(fused_out[i]).reshape(-1), seq_out[i].reshape(-1)
        )

    t_unfused_e2e = _time(unfused_e2e, reps)
    fused_e2e()  # warm (compiles happened above, but keep windows aligned)
    pack0 = fleet.timings["pack_s"]
    t0 = time.perf_counter()
    for _ in range(reps):
        fused_e2e()
    t_fused_e2e = (time.perf_counter() - t0) / reps
    # The pack_s delta covers exactly the `reps` timed rounds.
    pack_s = fleet.timings["pack_s"] - pack0

    # -- pallas backend: the batched fused-ingest megakernel ------------------
    # Same fleet contract, backend="pallas"; bitwise-asserted against the
    # sequential oracle, then timed (fewer reps -- interpret mode is the
    # expected-slower path on CPU; on TPU this is the compiled path).
    pallas_fleet = PixieFleet(default_grid=grid, batch_tile=n_apps,
                              backend="pallas")
    for n in names:
        pallas_fleet.config_for(n, grid)  # warm the config cache like `fleet`

    def pallas_e2e():
        return pallas_fleet.run_many(requests)

    pallas_out = pallas_e2e()
    for i in range(n_apps):
        np.testing.assert_array_equal(
            np.asarray(pallas_out[i]).reshape(-1), seq_out[i].reshape(-1)
        )
    pallas_reps = max(2, reps // 3)
    t_pallas_e2e = _time(pallas_e2e, pallas_reps)
    assert pallas_fleet.stats.overlay_builds == 1, pallas_fleet.stats.as_dict()
    assert pallas_fleet.stats.backend == "pallas"

    # -- mesh-sharded fused e2e: the 2-D (app x rows) scale-out axis ----------
    # The spec is requested unconditionally; hosts with too few local
    # devices degrade to the bitwise single-device fallback, and the
    # BENCH stamp records requested vs granted truthfully -- a dashboard
    # reading this JSON can never mistake a degraded fleet for a sharded
    # one.  (CI's mesh2d-parity job forces four host devices, so there
    # the 2x2 mesh is actually granted.)
    n_dev = len(jax.local_devices())
    mesh_spec = MeshSpec(app=2, rows=2) if n_dev >= 4 else MeshSpec(app=2)
    mesh_fleet = PixieFleet(default_grid=grid, batch_tile=n_apps,
                            mesh=mesh_spec)
    for n in names:
        mesh_fleet.config_for(n, grid)

    def mesh_e2e():
        return mesh_fleet.run_many(requests)

    mesh_out = mesh_e2e()
    for i in range(n_apps):
        np.testing.assert_array_equal(
            np.asarray(mesh_out[i]).reshape(-1), seq_out[i].reshape(-1)
        )
    t_mesh_e2e = _time(mesh_e2e, max(2, reps // 3))

    # pack fraction: share of the e2e cost spent *outside* the dispatch.
    pack_fraction_unfused = max(0.0, (t_unfused_e2e - t_seq) / t_unfused_e2e)
    pack_fraction_fused = pack_s / (reps * t_fused_e2e)

    # compile-once invariant: ONE fused overlay build for the grid, and
    # canvas tiling kept it at ONE XLA executable (-1 = this jax version
    # has no jit-cache introspection; overlay_builds is the stable counter).
    assert fleet.stats.overlay_builds == 2, fleet.stats.as_dict()  # fused + unfused
    assert fleet.overlay_executable_count(grid) in (2, -1), fleet.stats.as_dict()
    assert fleet.stats.fused_dispatches >= 1, fleet.stats.as_dict()
    assert fleet.stats.config_cache_hits >= n_apps, fleet.stats.as_dict()
    assert fleet.stats.stack_bank_hits >= 1, fleet.stats.as_dict()

    # plan-cache behavior of the fleet's overlay LRU (keyed by OverlayPlan):
    # hit rate ~1 after warmup is the compile-once contract at fleet scale.
    plan_lookups = fleet._overlays.hits + fleet._overlays.misses
    plan_cache = {
        "hits": fleet._overlays.hits,
        "misses": fleet._overlays.misses,
        "hit_rate": fleet._overlays.hits / plan_lookups if plan_lookups else 0.0,
        "plans": sorted(p.key() for p in fleet._overlays._d),
    }

    pixels = img.size * n_apps
    return {
        "bench": "fleet_throughput",
        "n_apps": n_apps,
        "image": [image_hw, image_hw],
        "grid": grid.name,
        "apps": names,
        "device_count": len(jax.local_devices()),
        "plan_cache": plan_cache,
        "sequential_s_per_round": t_seq,
        "batched_s_per_round": t_bat,
        "unfused_e2e_s_per_round": t_unfused_e2e,
        "fused_e2e_s_per_round": t_fused_e2e,
        "sequential_apps_per_s": n_apps / t_seq,
        "batched_apps_per_s": n_apps / t_bat,
        "unfused_e2e_apps_per_s": n_apps / t_unfused_e2e,
        "fused_e2e_apps_per_s": n_apps / t_fused_e2e,
        "sequential_mpixels_per_s": pixels / t_seq / 1e6,
        "batched_mpixels_per_s": pixels / t_bat / 1e6,
        "fused_e2e_mpixels_per_s": pixels / t_fused_e2e / 1e6,
        "speedup": t_seq / t_bat,
        "speedup_e2e": t_unfused_e2e / t_fused_e2e,
        "pack_fraction_unfused": pack_fraction_unfused,
        "pack_fraction_fused": pack_fraction_fused,
        "fleet_pack_s_per_round": pack_s / reps,
        "fleet_stats": fleet.stats.as_dict(),
        "overlay_executables": fleet.overlay_executable_count(grid),
        # per-backend fused e2e numbers, stable keys for the trajectory
        "backends": {
            "xla": {"fused_e2e_s_per_round": t_fused_e2e,
                    "fused_e2e_apps_per_s": n_apps / t_fused_e2e},
            "pallas": {"fused_e2e_s_per_round": t_pallas_e2e,
                       "fused_e2e_apps_per_s": n_apps / t_pallas_e2e,
                       "interpret_mode": default_interpret()},
        },
        "pallas_fused_e2e_apps_per_s": n_apps / t_pallas_e2e,
        "pallas_vs_xla_fused_e2e": t_fused_e2e / t_pallas_e2e,
        "pallas_floor_vs_xla": PALLAS_FLOOR_VS_XLA,
        "pallas_fleet_stats": pallas_fleet.stats.as_dict(),
        # Truthful mesh stamp (requested vs granted placement + the
        # degraded flag) -- serving dashboards read THIS, not the spec.
        "mesh": {
            "requested": list(mesh_fleet.stats.mesh_requested),
            "granted": list(mesh_fleet.stats.mesh_granted),
            "degraded": mesh_fleet.stats.mesh_degraded,
            "fused_e2e_s_per_round": t_mesh_e2e,
            "fused_e2e_apps_per_s": n_apps / t_mesh_e2e,
        },
        "mesh_fleet_stats": mesh_fleet.stats.as_dict(),
    }


def run_frames(n_apps: int, sizes, reps: int) -> dict:
    """The PR 5 plan-axes sweep: per frame size, fused e2e throughput of

      sync_untiled    tile_rows=None, ingest="sync"  (the PR 4 baseline)
      sync_tiled      tile_rows=side//4 (a real multi-tile split at every
                      size, unlike TILE_AUTO which stays untiled at smoke
                      sizes), ingest="sync"
      async_tiled     same tiling + the double-buffered ingest pipeline
                      (pooled donated canvases, lazy outputs)
      pallas_tiled    the same row tiling on backend="pallas": the tiled
                      megakernel with the PR 7 in-kernel double-buffered
                      HBM->VMEM DMA pipeline (interpret mode off-TPU; on
                      a TPU runner this measures the compiled
                      pallas/xla fused-e2e ratio the ISSUE asks for)

    All are bitwise-asserted against each other before timing.  Timed
    rounds call ``jax.block_until_ready`` on the outputs, so the async
    path's laziness is charged honestly -- its win must come from real
    pack/execute overlap, not deferred work escaping the clock.  The
    pallas variant is timed after the interleaved loop with its own
    (smaller) rep count: in interpret mode it is orders of magnitude off
    and would starve the interleaving.

    Each size also records an ``hbm_model`` column: the modelled
    per-frame HBM traffic (``tiling.hbm_read_model``) of the old
    host-pre-sliced slab layout vs the in-kernel DMA pipeline -- the
    ``1 + 2r/tile_rows`` read amplification (paid twice: slabs written,
    then streamed back) collapsing to ~1x seam re-reads and zero halo
    writes.
    """
    rng = np.random.default_rng(1)
    grid = sobel_grid()
    names = [FLEET_APPS[i % len(FLEET_APPS)] for i in range(n_apps)]
    frames = {}
    for side in sizes:
        img = rng.integers(0, 256, (side, side)).astype(np.int32)
        requests = [FleetRequest(app=n, image=img) for n in names]
        tile = max(8, side // 4)
        variants = {
            "sync_untiled": dict(ingest="sync", tile_rows=None),
            "sync_tiled": dict(ingest="sync", tile_rows=tile),
            "async_tiled": dict(ingest="async", tile_rows=tile),
        }
        # Larger frames amortize per-round overhead: fewer reps suffice
        # (but keep enough for the best-of estimator to settle).
        reps_side = max(8, reps // max(1, side // 32))
        itemsize = jnp.dtype(grid.dtype).itemsize
        entry = {
            "n_apps": n_apps,
            "tile_rows": tile,
            "auto_tile_rows": resolve_tile_rows(TILE_AUTO, side, side, 1, grid),
            "reps": reps_side,
            # Modelled per-frame HBM traffic of the two tiled lowerings
            # at this (side, tile): the old host-pre-sliced slab tensor
            # vs the PR 7 in-kernel DMA (seam re-reads only, no halo
            # writes).  ``hbm_bytes_read`` / ``read_amplification`` are
            # the trajectory columns.
            "hbm_model": {
                "presliced": hbm_read_model(side, side, 1, tile, itemsize,
                                            presliced=True),
                "dma": hbm_read_model(side, side, 1, tile, itemsize,
                                      presliced=False),
            },
        }
        # Warm every variant (compile + bitwise-assert), then time them
        # INTERLEAVED round-robin with a best-of estimator: scheduler load
        # on shared CI hosts drifts over seconds, so timing the variants
        # one after another would hand whichever ran during a quiet spell
        # a spurious win -- interleaving exposes all three to the same
        # noise and the min filters it.
        fleets, e2es, best = {}, {}, {}
        ref = None
        for key, axes in variants.items():
            fleet = PixieFleet(default_grid=grid, batch_tile=n_apps, **axes)

            def e2e(fleet=fleet):
                return jax.block_until_ready(fleet.run_many(requests))

            outs = e2e()   # warm + compile
            if ref is None:
                ref = [np.asarray(o) for o in outs]
            else:
                for a, b in zip(ref, outs):
                    np.testing.assert_array_equal(a, np.asarray(b))
            e2e()          # second warm round settles the canvas pool
            fleets[key], e2es[key], best[key] = fleet, e2e, float("inf")
        for _ in range(reps_side):
            for key, e2e in e2es.items():
                t0 = time.perf_counter()
                e2e()
                best[key] = min(best[key], time.perf_counter() - t0)
        for key, axes in variants.items():
            fleet, t = fleets[key], best[key]
            entry[key] = {
                "e2e_s_per_round": t,
                "e2e_apps_per_s": n_apps / t,
                "e2e_mpixels_per_s": n_apps * side * side / t / 1e6,
            }
            # compile-once must hold per variant (one fused plan each)
            assert fleet.stats.overlay_builds == 1, fleet.stats.as_dict()
            if axes["ingest"] == "async":
                entry[key]["canvas_pool_hits"] = fleet.stats.canvas_pool_hits
        entry["tiled_vs_untiled"] = (
            entry["sync_tiled"]["e2e_apps_per_s"]
            / entry["sync_untiled"]["e2e_apps_per_s"]
        )
        entry["async_vs_sync"] = (
            entry["async_tiled"]["e2e_apps_per_s"]
            / entry["sync_tiled"]["e2e_apps_per_s"]
        )

        # -- pallas tiled: the in-kernel DMA megakernel at this size ------
        # Bitwise-asserted, then timed on its own (fewer reps, not
        # interleaved): interpret mode off-TPU is the expected-slower
        # path; on a TPU runner this IS the compiled fused-e2e ratio.
        pallas_fleet = PixieFleet(default_grid=grid, batch_tile=n_apps,
                                  backend="pallas", tile_rows=tile)

        def pallas_e2e():
            return jax.block_until_ready(pallas_fleet.run_many(requests))

        for a, b in zip(ref, pallas_e2e()):
            np.testing.assert_array_equal(a, np.asarray(b))
        t_pallas = float("inf")
        for _ in range(max(2, reps_side // 8)):
            t0 = time.perf_counter()
            pallas_e2e()
            t_pallas = min(t_pallas, time.perf_counter() - t0)
        assert pallas_fleet.stats.overlay_builds == 1, \
            pallas_fleet.stats.as_dict()
        entry["pallas_tiled"] = {
            "e2e_s_per_round": t_pallas,
            "e2e_apps_per_s": n_apps / t_pallas,
            "e2e_mpixels_per_s": n_apps * side * side / t_pallas / 1e6,
            "interpret_mode": default_interpret(),
            "hbm_bytes_read": entry["hbm_model"]["dma"]["hbm_bytes_read"],
        }
        entry["pallas_vs_xla_tiled"] = (
            entry["pallas_tiled"]["e2e_apps_per_s"]
            / entry["sync_tiled"]["e2e_apps_per_s"]
        )
        frames[str(side)] = entry
    return frames


def main(argv=None) -> dict:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true", help="CI-sized quick run")
    p.add_argument("--n-apps", type=int, default=None)
    p.add_argument("--image", type=int, default=None, help="square image side")
    p.add_argument("--reps", type=int, default=None)
    p.add_argument("--out", type=str, default=None, help="write BENCH JSON here")
    p.add_argument("--frames", type=int, nargs="*", default=None,
                   help="sweep these square frame sides (bare flag: 32 128 "
                        "256) recording tiled-vs-untiled and sync-vs-async "
                        "fused e2e per size")
    p.add_argument("--check", action="store_true",
                   help="exit nonzero unless batched >= 2x sequential, fused "
                        "e2e >= 2x unfused e2e, pallas >= floor -- and, with "
                        "--frames, tiled >= 0.8x untiled at 32^2 and async "
                        ">= sync at 256^2")
    a = p.parse_args(argv)

    # Many small frames is the fleet's target regime (per-dispatch overhead
    # dominates); at large frames both paths converge on the same
    # compute-bound Mpx/s and batching only saves the dispatch tax.
    n_apps = a.n_apps or (8 if a.smoke else 16)
    image = a.image or 32
    reps = a.reps or (5 if a.smoke else 30)

    result = run(n_apps, image, reps)
    if a.frames is not None:
        result["frames"] = run_frames(n_apps, a.frames or [32, 128, 256], reps)
    print(f"fleet throughput: {n_apps} apps on {result['grid']}, "
          f"{image}x{image} px, {reps} reps")
    print(f"  sequential   {result['sequential_apps_per_s']:10.1f} apps/s   "
          f"{result['sequential_mpixels_per_s']:8.2f} Mpx/s   (dispatch only)")
    print(f"  batched      {result['batched_apps_per_s']:10.1f} apps/s   "
          f"{result['batched_mpixels_per_s']:8.2f} Mpx/s   (dispatch only)")
    print(f"  unfused e2e  {result['unfused_e2e_apps_per_s']:10.1f} apps/s   "
          f"(pack fraction {100*result['pack_fraction_unfused']:.0f}%)")
    print(f"  fused e2e    {result['fused_e2e_apps_per_s']:10.1f} apps/s   "
          f"(pack fraction {100*result['pack_fraction_fused']:.0f}%)")
    mode = "interpret" if result["backends"]["pallas"]["interpret_mode"] else "compiled"
    print(f"  pallas e2e   {result['pallas_fused_e2e_apps_per_s']:10.1f} apps/s   "
          f"(megakernel, {mode}; x{result['pallas_vs_xla_fused_e2e']:.2f} vs xla)")
    print(f"  speedup      x{result['speedup']:.2f} dispatch, "
          f"x{result['speedup_e2e']:.2f} e2e   "
          f"(overlay builds={result['fleet_stats']['overlay_builds']}, "
          f"xla executables={result['overlay_executables']})")
    print(f"  plan cache   hit rate {result['plan_cache']['hit_rate']:.2f} "
          f"over {len(result['plan_cache']['plans'])} plans, "
          f"{result['device_count']} device(s)")
    m = result["mesh"]
    state = "DEGRADED to" if m["degraded"] else "granted"
    print(f"  mesh e2e     {m['fused_e2e_apps_per_s']:10.1f} apps/s   "
          f"(requested {m['requested'][0]}x{m['requested'][1]}, {state} "
          f"{m['granted'][0]}x{m['granted'][1]})")
    for side, e in result.get("frames", {}).items():
        print(f"  {side:>4}^2 px    "
              f"untiled {e['sync_untiled']['e2e_apps_per_s']:8.1f}  "
              f"tiled(r{e['tile_rows']}) {e['sync_tiled']['e2e_apps_per_s']:8.1f}  "
              f"async {e['async_tiled']['e2e_apps_per_s']:8.1f}  "
              f"pallas {e['pallas_tiled']['e2e_apps_per_s']:8.1f} apps/s  "
              f"(x{e['tiled_vs_untiled']:.2f} tiled, "
              f"x{e['async_vs_sync']:.2f} async, "
              f"x{e['pallas_vs_xla_tiled']:.2f} pallas, "
              f"auto tile {e['auto_tile_rows']}, "
              f"hbm reads x{e['hbm_model']['dma']['read_amplification']:.2f} "
              f"dma vs "
              f"x{e['hbm_model']['presliced']['read_amplification']:.2f} "
              f"presliced)")

    print("BENCH " + json.dumps(result))
    if a.out:
        os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
        with open(a.out, "w") as f:
            json.dump(result, f, indent=2)
        print(f"wrote {a.out}")

    if a.check:
        fails = []
        if result["speedup"] < 2.0:
            fails.append(f"batched dispatch x{result['speedup']:.2f} < x2")
        if result["speedup_e2e"] < 2.0:
            fails.append(f"fused e2e x{result['speedup_e2e']:.2f} < x2")
        if result["pallas_vs_xla_fused_e2e"] < PALLAS_FLOOR_VS_XLA:
            fails.append(
                f"pallas fused e2e x{result['pallas_vs_xla_fused_e2e']:.3f} "
                f"of xla < floor x{PALLAS_FLOOR_VS_XLA}"
            )
        frames = result.get("frames", {})
        if "32" in frames and frames["32"]["tiled_vs_untiled"] < 0.8:
            # Tiling buys nothing at smoke sizes (the auto heuristic stays
            # untiled there); the floor only guards against the tiled
            # executors regressing catastrophically.
            fails.append(
                f"tiled fused e2e x{frames['32']['tiled_vs_untiled']:.2f} "
                f"of untiled at 32^2 < floor x0.8"
            )
        for side, e in frames.items():
            # The DMA pipeline's whole point, as a model invariant: fewer
            # modelled HBM bytes read than the pre-sliced slab layout at
            # every measured (side, tile), and ~1x frame-size reads.
            dma = e["hbm_model"]["dma"]
            pre = e["hbm_model"]["presliced"]
            if not (dma["hbm_bytes_read"] < pre["hbm_bytes_read"]
                    and dma["hbm_halo_bytes_written"] == 0
                    and dma["read_amplification"] < 1.5):
                fails.append(
                    f"hbm model at {side}^2: dma reads "
                    f"x{dma['read_amplification']:.2f} not < presliced "
                    f"x{pre['read_amplification']:.2f} (or halo writes "
                    f"nonzero)"
                )
        if "32" in frames and frames["32"]["pallas_vs_xla_tiled"] < PALLAS_FLOOR_VS_XLA:
            fails.append(
                f"pallas tiled fused e2e x"
                f"{frames['32']['pallas_vs_xla_tiled']:.3f} of xla tiled at "
                f"32^2 < floor x{PALLAS_FLOOR_VS_XLA}"
            )
        if "256" in frames:
            if frames["256"]["async_vs_sync"] < 1.0:
                fails.append(
                    f"async fused e2e x{frames['256']['async_vs_sync']:.2f} "
                    f"of sync at 256^2 < floor x1.0"
                )
            beats = (frames["256"]["async_tiled"]["e2e_apps_per_s"]
                     / frames["256"]["sync_untiled"]["e2e_apps_per_s"])
            if beats < 1.0:
                fails.append(
                    f"async+tiled fused e2e x{beats:.2f} of the sync "
                    f"untiled path at 256^2 < floor x1.0"
                )
        if fails:
            raise SystemExit("FAIL: " + "; ".join(fails))
    return result


if __name__ == "__main__":
    main()
