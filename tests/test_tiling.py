"""Pixel-axis row tiling + async ingest: the two PR 5 OverlayPlan axes.

The row-tiled fused executors (the ``lax.dynamic_slice``-based XLA twin
and the slab-tiled Pallas megakernel) must be *bitwise* identical to the
untiled sync XLA oracle -- across tile heights that do not divide H,
tile_rows >= H, radius-0 tap grids, ragged non-square multi-tenant
stacks, and both backends.  The async double-buffered ingest pipeline
must likewise be bitwise-equal to sync (only buffer lifetime and
laziness differ).  The ``slow``-marked 256x256 suites are the
large-frame-parity CI gate: tiling + async at real frame sizes,
composing with the PR 4 sharded path under two forced host devices.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import OverlayPlan, compile_plan, map_app, sobel_grid
from repro.core import applications as apps
from repro.core import interpreter
from repro.core.bitstream import VCGRAConfig
from repro.core.ingest import IngestPlan, check_ingest, tap_offsets
from repro.core.tiling import (
    DEFAULT_VMEM_BUDGET_BYTES,
    TILE_AUTO,
    num_row_tiles,
    resolve_tile_rows,
    slab_rows_per_budget,
)
from repro.kernels.vcgra.ops import _batched_fused_pallas_fn
from repro.runtime.fleet import FleetRequest, PixieFleet

GRID = sobel_grid()
MULTI_DEVICE = len(jax.local_devices()) >= 2
needs_two_devices = pytest.mark.skipif(
    not MULTI_DEVICE,
    reason="needs >= 2 local devices (CI large-frame-parity job forces 2 "
    "via XLA_FLAGS=--xla_force_host_platform_device_count=2)",
)

FLEET_APPS = ["sobel_x", "sobel_y", "sharpen", "laplace", "threshold", "identity"]
# Place/route once per app; every test below only swaps settings arrays.
CONFIGS = {n: map_app(apps.ALL_APPS[n](), GRID) for n in FLEET_APPS}


def _stacked_workload(rng, names, hws):
    """Ragged non-square frames embedded on one canvas + stacked settings
    (same construction as the fleet's fused dispatch)."""
    images = [rng.integers(0, 256, hw).astype(np.int32) for hw in hws]
    configs = [CONFIGS[n] for n in names]
    Hb, Wb = max(h for h, _ in hws), max(w for _, w in hws)
    canvas = np.zeros((len(names), Hb, Wb), dtype=np.int32)
    for i, img in enumerate(images):
        canvas[i, : img.shape[0], : img.shape[1]] = img
    return (
        VCGRAConfig.stack(configs),
        IngestPlan.stack([c.ingest for c in configs], GRID.dtype),
        jnp.asarray(canvas),
    )


# -- plan axis validation ------------------------------------------------------


def test_tile_rows_plan_validation():
    with pytest.raises(ValueError, match="unfused"):
        OverlayPlan(grid=GRID, batched=True, tile_rows=8)
    with pytest.raises(ValueError, match="tile_rows"):
        OverlayPlan(grid=GRID, fused=True, tile_rows=0)
    with pytest.raises(ValueError, match="unknown ingest"):
        OverlayPlan(grid=GRID, ingest="dma")
    with pytest.raises(ValueError, match="unknown ingest"):
        check_ingest("eager")
    # canonicalization: explicit heights become ints, auto survives
    assert OverlayPlan(grid=GRID, fused=True, tile_rows="7").tile_rows == 7
    assert OverlayPlan(grid=GRID, fused=True, tile_rows=TILE_AUTO).tile_rows == TILE_AUTO
    # the fleet validates eagerly at construction, not on the first flush
    for bad in (0, -3, "bogus"):
        with pytest.raises(ValueError, match="tile_rows"):
            PixieFleet(tile_rows=bad)
    with pytest.raises(ValueError, match="unknown ingest"):
        PixieFleet(ingest="dma")


def test_tile_and_ingest_axes_distinguish_plan_keys():
    base = OverlayPlan(grid=GRID, batched=True, fused=True)
    variants = [
        base,
        OverlayPlan(grid=GRID, batched=True, fused=True, tile_rows=8),
        OverlayPlan(grid=GRID, batched=True, fused=True, tile_rows=16),
        OverlayPlan(grid=GRID, batched=True, fused=True, tile_rows=TILE_AUTO),
        OverlayPlan(grid=GRID, batched=True, fused=True, ingest="async"),
        OverlayPlan(grid=GRID, batched=True, fused=True, tile_rows=8,
                    ingest="async"),
    ]
    assert len({hash(p) for p in variants}) == len(variants)
    assert len({p.key() for p in variants}) == len(variants)
    # PR 4-era keys are stable: default tile/ingest add no segments
    assert base.key().endswith("dev1")
    assert "tile:8" in variants[1].key() and "async" in variants[4].key()


def test_resolve_tile_rows_and_budget_heuristic():
    # None = untiled (one slab covering the frame); ints clamp to [1, H]
    assert resolve_tile_rows(None, 33, 5, 1, GRID) == 33
    assert resolve_tile_rows(64, 10, 5, 1, GRID) == 10
    assert resolve_tile_rows(3, 10, 5, 1, GRID) == 3
    # auto: smoke-sized frames fit the budget whole (degenerates untiled) ...
    assert resolve_tile_rows(TILE_AUTO, 32, 32, 1, GRID) == 32
    # ... 1080p-class frames do not: the heuristic actually tiles
    auto_1080 = resolve_tile_rows(TILE_AUTO, 1080, 1920, 1, GRID)
    assert 1 <= auto_1080 < 1080
    # the working set the pick implies respects the budget, INCLUDING both
    # in-flight DMA slabs of the double buffer (+2 rows per output row plus
    # the constant 2 * 2r * W halo rows), the double-buffered output block
    # and the block-sized channel/level buffers of the datapath
    itemsize = jnp.dtype(GRID.dtype).itemsize
    taps = (2 * 1 + 1) ** 2
    per_row = (taps + 2 + 2 * GRID.num_outputs) * 1920 * itemsize
    fixed = (2 * (2 * 1) * 1920 + (GRID.num_inputs
             + 2 * max(GRID.pes_per_level)) * 8 * 512) * itemsize
    assert auto_1080 * per_row + fixed <= DEFAULT_VMEM_BUDGET_BYTES
    # budget monotonicity + floor of one row
    assert slab_rows_per_budget(1 << 20, 2, num_inputs=64, max_level_width=32,
                                itemsize=4) == 1
    assert num_row_tiles(13, 4) == 4 and num_row_tiles(12, 4) == 3


# -- bitwise parity vs the untiled sync XLA oracle -----------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("tile_rows", [1, 3, 5, 8, 64, TILE_AUTO])
def test_tiled_matches_untiled_oracle_bitwise(backend, tile_rows, rng):
    """compile_plan(tile_rows=...) == the untiled XLA step, bitwise, on a
    ragged non-square stack with H=13 (so 3, 5 and 8 do not divide H and
    64 exceeds it)."""
    names = ["sobel_x", "sharpen", "identity", "laplace"]
    hws = [(13, 11), (9, 4), (7, 7), (3, 10)]
    stacked, ingests, canvas = _stacked_workload(rng, names, hws)
    hw = np.asarray(hws, np.int32)
    oracle = np.asarray(interpreter.batched_fused_overlay_step(
        GRID, 1, stacked, ingests, canvas, hw))
    exe = compile_plan(OverlayPlan(grid=GRID, batched=True, fused=True,
                                   backend=backend, tile_rows=tile_rows))
    np.testing.assert_array_equal(
        np.asarray(exe(stacked, ingests, hw, canvas)), oracle
    )


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_fleet_tiled_bitwise(backend, rng):
    """PixieFleet(tile_rows=4) == PixieFleet(tile_rows=None) on ragged
    frames; the tiled fleet stamps the tile segment into its plan keys."""
    names = ["sobel_x", "sharpen", "identity"]
    images = [rng.integers(0, 256, hw).astype(np.int32)
              for hw in [(6, 8), (11, 5), (3, 9)]]
    reqs = [FleetRequest(app=n, image=i) for n, i in zip(names, images)]
    ref = PixieFleet(default_grid=GRID, backend=backend,
                     tile_rows=None).run_many(reqs)
    fleet = PixieFleet(default_grid=GRID, backend=backend, tile_rows=4)
    got = fleet.run_many(reqs)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    assert all("tile:4" in k for k in fleet.stats.dispatch_plans)


def test_async_ingest_single_flush_bitwise(rng):
    img = rng.integers(0, 256, (16, 16)).astype(np.int32)
    reqs = [FleetRequest(app=n, image=img) for n in FLEET_APPS]
    ref = PixieFleet(default_grid=GRID).run_many(reqs)
    fleet = PixieFleet(default_grid=GRID, ingest="async")
    got = fleet.run_many(reqs)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert fleet.stats.ingest == "async"
    assert all("async" in k for k in fleet.stats.dispatch_plans)


# -- deterministic edge-case sweep (the hypothesis twin lives in
#    test_tiling_property.py, gated on the dev dependency) --------------------


def random_fused_workload(H, W, radius, n, seed):
    """Random frames + random *runtime* ingest settings: tap selects drawn
    over the whole radius-``radius`` bank (zero row included) and random
    const values -- the tiled executors must agree with the oracle for any
    settings, not just the library apps' plans.  Shared with the
    hypothesis suite (test_tiling_property.py)."""
    rng = np.random.default_rng(seed)
    configs = [CONFIGS[FLEET_APPS[i % len(FLEET_APPS)]] for i in range(n)]
    stacked = VCGRAConfig.stack(configs)
    taps = len(tap_offsets(radius))
    tap_sel = jnp.asarray(
        rng.integers(0, taps + 1, (n, GRID.num_inputs)).astype(np.int32)
    )
    const_vals = jnp.asarray(
        rng.integers(-8, 9, (n, GRID.num_inputs)), GRID.dtype
    )
    images = jnp.asarray(rng.integers(0, 256, (n, H, W)).astype(np.int32))
    return stacked, (tap_sel, const_vals), images


def assert_tiled_equals_untiled(H, W, radius, tile_rows, n, seed, backend):
    """One tiled-vs-untiled bitwise check over random runtime settings;
    the body of both the deterministic sweep and the hypothesis suite."""
    stacked, ingests, images = random_fused_workload(H, W, radius, n, seed)
    oracle = np.asarray(interpreter.batched_fused_overlay_step(
        GRID, radius, stacked, ingests, images))
    if backend == "xla":
        tiled = interpreter.tiled_batched_fused_overlay_step(
            GRID, radius, tile_rows, stacked, ingests, images)
    else:
        tiled = _batched_fused_pallas_fn(
            GRID, radius, tile_rows=tile_rows)(stacked, ingests, None, images)
    np.testing.assert_array_equal(np.asarray(tiled), oracle)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize(
    "H,W,radius,tile_rows",
    [
        (1, 1, 0, 1),     # degenerate frame, radius-0 single-tap bank
        (7, 5, 0, 3),     # radius-0, tile does not divide H
        (13, 9, 1, 5),    # classic ragged tiling
        (6, 11, 1, 6),    # tile_rows == H (single tile, exact)
        (4, 7, 2, 3),     # radius exceeds tile_rows: halo > tile body
        (9, 3, 2, 64),    # tile_rows >> H clamps to untiled
        # The Pallas layout (interpret mode runs the one the TPU compiles):
        # whole sublane tiles, lane-padded canvas, 8 x {512,256,128} blocks.
        (5, 30, 1, None),     # W < LANE: one padded tile
        (13, 256, 1, 8),      # 256-lane blocks, ragged bottom tile
        (9, 200, 2, None),    # width padded 200 -> 256 lanes
        (10, 512, 1, None),   # 512-lane blocks
        (17, 640, 0, 8),      # 5 x 128-lane blocks, 3 tiles
    ],
)
def test_tiled_edge_cases_bitwise(H, W, radius, tile_rows, backend):
    assert_tiled_equals_untiled(H, W, radius, tile_rows, n=3, seed=7,
                                backend=backend)


# -- large-frame parity (the CI gate at 256x256) -------------------------------


@pytest.mark.slow
def test_large_frame_tiled_async_parity_256(rng):
    """256x256 frames: auto-tiled async fleet == untiled sync fleet,
    bitwise, on both dispatch paths of a mixed flush."""
    side = 256
    names = ["sobel_x", "sharpen", "identity"]
    reqs = [FleetRequest(app=n, image=rng.integers(0, 256, (side, side))
                         .astype(np.int32)) for n in names]
    reqs.append(FleetRequest(
        app="threshold",
        inputs={"p11": rng.integers(0, 256, (257,)).astype(np.int32)},
    ))
    ref = PixieFleet(default_grid=GRID, tile_rows=None).run_many(reqs)
    fleet = PixieFleet(default_grid=GRID, tile_rows=TILE_AUTO, ingest="async")
    # Async pool depth is 2 (double buffer): the third flush is the first
    # to rotate back onto a pooled canvas.
    for _ in range(3):
        got = fleet.run_many(reqs)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert fleet.stats.canvas_pool_hits >= 1


@pytest.mark.slow
@needs_two_devices
def test_large_frame_tiled_sharded_parity_256(rng):
    """Tiling + async ingest compose with the PR 4 app-axis sharding:
    devices=2 tiled async == single-device untiled sync at 256x256."""
    side = 256
    names = ["sobel_x", "laplace"]
    reqs = [FleetRequest(app=n, image=rng.integers(0, 256, (side, side))
                         .astype(np.int32)) for n in names]
    ref = PixieFleet(default_grid=GRID, tile_rows=None).run_many(reqs)
    fleet = PixieFleet(default_grid=GRID, devices=2, tile_rows=64,
                       ingest="async")
    got = fleet.run_many(reqs)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert all("dev2" in k and "tile:64" in k and "async" in k
               for k in fleet.stats.dispatch_plans)
