"""The in-kernel double-buffered HBM->VMEM DMA pipeline (PR 7).

The tiled Pallas megakernel no longer receives host-pre-sliced halo
slabs: the pallas grid walks row tiles over the ONE zero-row-padded
frame stack and the kernel's own ``make_async_copy`` double buffer
streams each ``[tile_rows + 2r, W]`` halo window HBM->VMEM, prefetching
tile t+1 while tile t computes.  This suite pins the contract:

* bitwise parity with the untiled XLA oracle in interpret mode, over a
  hypothesis sweep of (H, W, radius, tile_rows) covering radius=0,
  tile_rows >= H, tile_rows not dividing H and non-square frames (the
  deterministic corner sweep rides test_tiling.py, which routes the same
  DMA kernel);
* the grep-lint acceptance criterion: ``halo_row_slabs`` has NO call
  site in the kernel package -- the pre-slice survives only as the XLA
  twin's layout (``core/interpreter.py``);
* plan-compatibility: the DMA lowering is the compiled-TPU realization
  of the EXISTING ``tile_rows`` plan axis -- same plan keys and hashes,
  no new axis, so every PR 5-era cache entry stays valid and repeat
  dispatches hit the fleet's overlay LRU;
* the kernels' tile-height alignment (whole sublane tiles) lives in
  ``tiling.resolve_tile_rows`` (one definition with the heuristic and
  the XLA twin);
* per-device canvas pooling for sharded async fleets (the PR 5 pointer
  satellite): devices=2 async flushes fill and ship one pooled buffer
  per mesh device, counted in ``FleetStats.canvas_pool_device_hits``,
  bitwise-equal to the single-device sync run.

The compiled kernel is checked against the TPU compiler in
test_tpu_compile.py and bitwise against the XLA fleet on the chip by
chip_smoke.py.
"""

import re
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import OverlayPlan, compile_plan, sobel_grid
from repro.core.plan import PipelineSpec
from repro.core.tiling import (
    SUBLANE,
    TILE_AUTO,
    aligned_tile_rows,
    resolve_tile_rows,
)
from repro.runtime.fleet import FleetRequest, PixieFleet

from test_pipeline import GRID as PIPE_GRID, _stage_settings, chain_configs
from test_tiling import assert_tiled_equals_untiled, needs_two_devices

GRID = sobel_grid()
REPO = Path(__file__).resolve().parent.parent


# -- acceptance grep-lint: no host-side halo pre-slice on the pallas path ------


def test_no_halo_row_slabs_call_in_kernel_package():
    """``halo_row_slabs`` must have zero call sites under
    ``src/repro/kernels/`` -- the megakernel's halo windows are sliced by
    the in-kernel DMA, never materialized in HBM.  The XLA tiled twin
    (core/interpreter.py) legitimately keeps the pre-slice: on CPU there
    is no VMEM and the duplicated slab tensor buys XLA fusion."""
    call = re.compile(r"\bhalo_row_slabs\s*\(")
    offenders = []
    for path in sorted((REPO / "src" / "repro" / "kernels").rglob("*.py")):
        text = path.read_text(encoding="utf-8")
        for m in call.finditer(text):
            line = text.count("\n", 0, m.start()) + 1
            offenders.append(f"{path.relative_to(REPO)}:{line}")
    assert not offenders, (
        "host-side halo pre-slice called from the kernel package -- the "
        "pallas path streams halo windows with the in-kernel DMA double "
        "buffer: " + ", ".join(offenders)
    )


# -- plan-axis compatibility: same keys, same cache entries --------------------


def test_dma_path_reuses_tile_rows_plan_entries():
    """The DMA lowering changed the kernel, not the plan: pallas tiled
    plans keep their PR 5 keys (no new axis segment) and a fleet's repeat
    tiled dispatches hit the SAME overlay LRU entry."""
    plan = OverlayPlan(grid=GRID, batched=True, fused=True,
                       backend="pallas", tile_rows=8)
    # PR 5-era key shape: the tile segment, nothing DMA-specific.
    assert plan.key() == f"{GRID.name}|batched|fused:r1|pallas|dev1|tile:8"
    assert plan == OverlayPlan(grid=GRID, batched=True, fused=True,
                               backend="pallas", tile_rows=8)
    fleet = PixieFleet(default_grid=GRID, backend="pallas", tile_rows=8)
    img = np.arange(48, dtype=np.int32).reshape(6, 8)
    fleet.run_many([FleetRequest(app="sobel_x", image=img)])
    fleet.run_many([FleetRequest(app="sharpen", image=img)])
    assert fleet.stats.overlay_builds == 1
    assert fleet.stats.overlay_cache_hits >= 1
    assert all("tile:8" in k for k in fleet.stats.dispatch_plans)


def test_lane_alignment_is_resolved_in_tiling():
    """One rounding definition: an AUTO pick that actually tiles, asked
    with ``align=SUBLANE``, is whole sublane tiles (the compiled kernel's
    block rule) and equals ``aligned_tile_rows`` of the unaligned pick --
    and the interpret path (align=None) is untouched."""
    H, W = 4096, 1920
    raw = resolve_tile_rows(TILE_AUTO, H, W, 1, GRID)
    aligned = resolve_tile_rows(TILE_AUTO, H, W, 1, GRID, align=SUBLANE)
    assert 1 <= aligned < H and aligned % SUBLANE == 0
    assert aligned == aligned_tile_rows(raw, SUBLANE)
    assert aligned <= raw
    # degenerate-untiled AUTO picks are not rounded (single slab == whole
    # frame needs no tiling machinery; the kernel pads it to whole tiles)
    assert resolve_tile_rows(TILE_AUTO, 32, 32, 1, GRID, align=SUBLANE) == 32
    # explicit tile heights that tile are rounded the same way for the
    # kernels, and taken verbatim by the XLA twin (align=None)
    assert resolve_tile_rows(5, 100, 7, 1, GRID, align=SUBLANE) == SUBLANE
    assert resolve_tile_rows(20, 100, 7, 1, GRID, align=SUBLANE) == 16
    assert resolve_tile_rows(5, 6, 7, 1, GRID, align=SUBLANE) == 6  # one tile
    assert resolve_tile_rows(5, 100, 7, 1, GRID) == 5


# -- hypothesis sweep: DMA kernel (interpret) vs the untiled XLA oracle --------

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - dev dependency absent
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:

    @st.composite
    def dma_cases(draw):
        """Random (H, W, radius, tile_rows) hitting the DMA corner cases
        by construction: radius 0 (single-tap bank, pure-body windows),
        tile_rows >= H (single tile, warm-up DMA only), tile_rows not
        dividing H (ragged bottom tile reads the zero pad as halo), and
        non-square frames (W != H exercises the column axis of the
        windows); odd tile counts stress the linearized-step slot
        rotation at app boundaries."""
        H = draw(st.integers(1, 16))
        W = draw(st.integers(1, 16))
        radius = draw(st.integers(0, 2))
        tile_rows = draw(st.integers(1, H + 3))
        n = draw(st.integers(1, 3))
        seed = draw(st.integers(0, 2**31 - 1))
        return H, W, radius, tile_rows, n, seed

    @settings(max_examples=10, deadline=None)
    @given(dma_cases())
    def test_property_dma_kernel_bitwise_vs_oracle(case):
        H, W, radius, tile_rows, n, seed = case
        assert_tiled_equals_untiled(H, W, radius, tile_rows, n, seed,
                                    backend="pallas")

else:  # pragma: no cover - dev dependency absent

    def test_property_dma_kernel_bitwise_vs_oracle():
        pytest.skip("hypothesis not installed (see requirements-dev.txt)")


def test_dma_multi_tile_multi_app_odd_tiles_bitwise():
    """The regression corner the double buffer is most likely to break:
    several apps x an ODD number of row tiles per app, where a slot
    rotation keyed on the tile index alone (instead of the linearized
    step) desynchronizes the prefetch at every app boundary."""
    # H=21, tile_rows=8 -> 3 tiles/app; 4 apps -> 12 steps, odd per-app.
    assert_tiled_equals_untiled(21, 6, 1, 8, n=4, seed=11, backend="pallas")


# -- the chain kernel's tiled layout, run in interpret mode --------------------


@pytest.mark.parametrize("tile_rows", [None, 8])
def test_chain_kernel_layout_bitwise_vs_xla(tile_rows, rng):
    """The chain megakernel's rounded trapezoid rows and padded lanes on
    a ragged stack wider than one lane block: bitwise equal to the XLA
    chain executor."""
    spec = PipelineSpec.chain(chain_configs())
    hws = [(21, 200), (13, 150)]
    canvas = np.zeros((2, 21, 200), np.int32)
    for i, (h, w) in enumerate(hws):
        canvas[i, :h, :w] = rng.integers(0, 256, (h, w))
    args = (_stage_settings([spec] * 2),
            jnp.asarray(np.asarray(hws, np.int32)), jnp.asarray(canvas))
    want = compile_plan(OverlayPlan(
        grid=PIPE_GRID, batched=True, pipeline=(spec,) * 2))(*args)
    got = compile_plan(OverlayPlan(
        grid=PIPE_GRID, batched=True, pipeline=(spec,) * 2, backend="pallas",
        tile_rows=tile_rows))(*args)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- per-device canvas pool (sharded async fleets) -----------------------------


@needs_two_devices
def test_sharded_async_per_device_canvas_pool_bitwise(rng):
    """devices=2 async fused flushes pool and ship one canvas per mesh
    device; after the depth-2 rotation warms up, BOTH devices count
    reuse hits, and outputs stay bitwise-equal to the single-device sync
    fleet."""
    names = ["sobel_x", "sharpen", "laplace", "identity"]
    reqs = [FleetRequest(app=n, image=rng.integers(0, 256, (16, 16))
                         .astype(np.int32)) for n in names]
    ref = PixieFleet(default_grid=GRID).run_many(reqs)
    fleet = PixieFleet(default_grid=GRID, devices=2, ingest="async")
    # Per-device pool depth is 2: the third flush is the first to rotate
    # every device back onto a pooled buffer.
    for _ in range(3):
        got = fleet.run_many(reqs)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    hits = fleet.stats.canvas_pool_device_hits
    assert sorted(hits) == ["0", "1"], hits
    assert all(v >= 1 for v in hits.values())
    assert fleet.stats.canvas_pool_hits >= sum(hits.values())


def test_unsharded_fleet_has_no_device_hits(rng):
    """The per-device counters stay empty off-mesh: the unsharded async
    path keeps the single whole-batch canvas."""
    fleet = PixieFleet(default_grid=GRID, ingest="async")
    reqs = [FleetRequest(app="sobel_x",
                         image=rng.integers(0, 256, (8, 8)).astype(np.int32))]
    for _ in range(3):
        fleet.run_many(reqs)
    assert fleet.stats.canvas_pool_device_hits == {}
    assert fleet.stats.canvas_pool_hits >= 1
