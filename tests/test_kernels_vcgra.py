"""Kernel tests: VCGRA Pallas executor (specialized + conventional) vs the
pure-jnp oracle, swept over applications, shapes and dtypes -- plus the
batched fused-ingest and chain megakernels (N tenants, raw frames, one
pallas_call) vs the batched interpreter oracle, at frame extents that
leave whole row tiles and app slots empty."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import shared_app_grid

from repro.core import OverlayPlan, compile_plan, for_dfg, map_app, sobel_grid
from repro.core import applications as apps
from repro.core.bitstream import VCGRAConfig
from repro.core.grid import rectangular
from repro.core.ingest import IngestPlan
from repro.core.interpreter import (
    batched_fused_overlay_step,
    batched_overlay_step,
    pack_inputs,
    pad_channels,
)
from repro.core.ops import Op, apply_generic
from repro.core.plan import PipelineSpec
from repro.kernels.vcgra import (
    default_interpret,
    grid_steps,
    make_batched_fused_pallas_fn,
    make_batched_pallas_fn,
    pack_settings_batched,
    vcgra_apply,
    vcgra_apply_image,
    vcgra_ref,
)
from repro.kernels.vcgra.vcgra_kernel import _pack_settings, vcgra_batched

from test_pipeline import GRID as CHAIN_GRID, _stage_settings, chain_configs


def _setup(app_name, data_bits=32, float_pe=False, shape="exact"):
    dfg = apps.ALL_APPS[app_name]()
    grid = for_dfg(dfg, shape=shape, data_bits=data_bits, float_pe=float_pe)
    cfg = map_app(dfg, grid)
    return dfg, grid, cfg


@pytest.mark.parametrize("app_name", ["sobel_x", "sobel_mag", "gauss3", "threshold"])
@pytest.mark.parametrize("mode", ["specialized", "conventional"])
@pytest.mark.parametrize(
    "hw", [(8, 16), (16, 128), (30, 67)]  # aligned and ragged image shapes
)
def test_kernel_matches_ref_int(app_name, mode, hw, rng):
    dfg, grid, cfg = _setup(app_name)
    img = jnp.asarray(rng.integers(0, 256, hw).astype(np.int32))
    taps = apps.stencil_inputs(img)
    feed = {k: v for k, v in taps.items() if k in cfg.input_order}
    x = pack_inputs(cfg, feed, grid.dtype)
    ref = np.asarray(vcgra_ref(grid, cfg, x))
    out = np.asarray(vcgra_apply(grid, cfg, x, mode=mode, block_n=256))
    np.testing.assert_array_equal(out, ref)


@pytest.mark.parametrize("mode", ["specialized", "conventional"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_ref_float(mode, dtype, rng):
    dfg = apps.sobel_magnitude()
    grid = for_dfg(dfg, shape="exact", float_pe=True, data_bits=32)
    cfg = map_app(dfg, grid)
    img = jnp.asarray(rng.random((16, 32)).astype(np.float32) * 100).astype(dtype)
    taps = apps.stencil_inputs(img)
    x = pack_inputs(cfg, taps, dtype)
    ref = np.asarray(vcgra_ref(grid, cfg, x).astype(jnp.float32))
    out = np.asarray(
        vcgra_apply(grid, cfg, x, mode=mode, block_n=128).astype(jnp.float32)
    )
    tol = 1e-6 if dtype == jnp.float32 else 0.5
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)


@pytest.mark.parametrize("block_n", [128, 256, 1024])
def test_kernel_block_size_sweep(block_n, rng):
    dfg, grid, cfg = _setup("sobel_x")
    img = jnp.asarray(rng.integers(0, 256, (24, 53)).astype(np.int32))
    out = np.asarray(vcgra_apply_image(grid, cfg, img, block_n=block_n))
    np.testing.assert_array_equal(out, apps.conv2d_reference(np.asarray(img), apps.SOBEL_X))


def test_kernel_on_rect_grid_with_none_pes(rng):
    """Fig. 5 mapping (45-PE rect grid, 25 NONE PEs) through the kernel."""
    dfg = apps.sobel_x()
    grid = sobel_grid()
    cfg = map_app(dfg, grid)
    img = jnp.asarray(rng.integers(0, 256, (12, 12)).astype(np.int32))
    out = np.asarray(vcgra_apply_image(grid, cfg, img, mode="specialized", block_n=128))
    np.testing.assert_array_equal(out, apps.conv2d_reference(np.asarray(img), apps.SOBEL_X))
    out_c = np.asarray(
        vcgra_apply_image(grid, cfg, img, mode="conventional", block_n=128)
    )
    np.testing.assert_array_equal(out_c, out)


def test_conventional_settings_pack_roundtrip():
    dfg, grid, cfg = _setup("sobel_mag")
    ops_arr, sel_arr, out_sel, max_w = _pack_settings(grid, cfg)
    assert ops_arr.shape == (grid.num_levels, max_w)
    assert sel_arr.shape == (grid.num_levels, max_w, 2)
    for lvl in range(grid.num_levels):
        w = grid.pes_per_level[lvl]
        np.testing.assert_array_equal(np.asarray(ops_arr)[lvl, :w], cfg.opcodes[lvl])
        np.testing.assert_array_equal(np.asarray(sel_arr)[lvl, :w], cfg.selects[lvl])


# -- PE opcode dispatch ------------------------------------------------------

#: One PE on two memory inputs: the kernel's opcode branch in isolation.
PE_GRIDS = {
    "int32": rectangular("one-pe-int", 2, 1, 1),
    "float32": rectangular("one-pe-float", 2, 1, 1, float_pe=True),
}
#: Operand values: zeros (DIV by zero) and negatives (floor division).
PE_VALUES = {
    "int32": np.array([-9, -7, -2, -1, 0, 1, 2, 3, 7, 100], np.int32),
    "float32": np.array([-7.25, -2.0, -1.0, -0.5, -0.0, 0.0, 0.5, 1.0, 3.0,
                         100.0], np.float32),
}


@functools.lru_cache(maxsize=None)
def _one_pe_kernel(dtype_name):
    """The conventional kernel jitted once per grid: each opcode is SMEM
    data, so every case of a grid reuses one executable."""
    return jax.jit(functools.partial(vcgra_batched, PE_GRIDS[dtype_name],
                                     interpret=True))


@pytest.mark.parametrize("dtype_name", sorted(PE_GRIDS))
@pytest.mark.parametrize("opcode", [*range(len(Op)), len(Op), -1])
def test_conventional_kernel_opcode_matches_apply_generic(dtype_name, opcode):
    """Each opcode -- every unit, NONE, MAC and out-of-range codes -- takes
    its scalar branch in the kernel and is bitwise equal to the XLA
    interpreter's per-lane mux on every operand pair."""
    grid = PE_GRIDS[dtype_name]
    vals = PE_VALUES[dtype_name]
    a, b = (v.ravel() for v in np.meshgrid(vals, vals, indexing="ij"))
    settings = (jnp.full((1, 1, 1), opcode, jnp.int32),
                jnp.asarray([[[[0, 1]]]], jnp.int32),
                jnp.zeros((1, 1), jnp.int32))
    x = jnp.asarray(np.stack([a, b]))[None]
    got = _one_pe_kernel(dtype_name)(settings, x)
    want = apply_generic(jnp.asarray(opcode, jnp.int32), jnp.asarray(a),
                         jnp.asarray(b))
    got, want = np.asarray(got)[0, 0], np.asarray(want)
    assert got.dtype == want.dtype == grid.dtype
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))


# -- batched fused-ingest megakernel ------------------------------------------

MEGA_NAMES = sorted(apps.ALL_APPS)
MEGA_GRID = shared_app_grid(MEGA_NAMES, name="megakernel-shared")


def test_default_interpret_is_platform_aware():
    """interpret=None auto-detects: interpreted everywhere except real TPU
    (the satellite fix for the unconditional interpret=True default)."""
    on_tpu = jax.default_backend() == "tpu"
    assert default_interpret() is (not on_tpu)


def test_pack_settings_batched_dense_banks():
    """Dense SMEM banks agree with the per-app `_pack_settings` rows and
    zero-fill (Op.NONE) the pad slots beyond each level's true width."""
    configs = [map_app(apps.ALL_APPS[n](), MEGA_GRID) for n in ["sobel_x", "gauss3"]]
    ops_d, sel_d, out_d = pack_settings_batched(
        MEGA_GRID, VCGRAConfig.stack(configs)
    )
    max_w = max(MEGA_GRID.pes_per_level)
    n, L = len(configs), MEGA_GRID.num_levels
    assert ops_d.shape == (n, L, max_w) and sel_d.shape == (n, L, max_w, 2)
    assert out_d.shape == (n, MEGA_GRID.num_outputs)
    for i, cfg in enumerate(configs):
        ref_ops, ref_sel, ref_out, _ = _pack_settings(MEGA_GRID, cfg)
        np.testing.assert_array_equal(np.asarray(ops_d)[i], np.asarray(ref_ops))
        np.testing.assert_array_equal(np.asarray(sel_d)[i], np.asarray(ref_sel))
        np.testing.assert_array_equal(np.asarray(out_d)[i], np.asarray(ref_out))
        for lvl in range(L):
            w = MEGA_GRID.pes_per_level[lvl]
            assert not np.asarray(ops_d)[i, lvl, w:].any()


def test_megakernel_fused_batched_matches_interpreter_all_apps(rng):
    """The tentpole invariant: every library app stacked into ONE fused
    megakernel dispatch over ragged non-square frames is bitwise equal to
    the XLA batched fused interpreter (itself the tested oracle)."""
    images = [
        rng.integers(0, 256, (6 + 2 * i, 19 - i)).astype(np.int32)
        for i in range(len(MEGA_NAMES))
    ]
    configs = [map_app(apps.ALL_APPS[n](), MEGA_GRID) for n in MEGA_NAMES]
    Hb = max(i.shape[0] for i in images)
    Wb = max(i.shape[1] for i in images)
    canvas = np.zeros((len(MEGA_NAMES), Hb, Wb), dtype=np.int32)
    for i, img in enumerate(images):
        canvas[i, : img.shape[0], : img.shape[1]] = img

    stacked = VCGRAConfig.stack(configs)
    ingests = IngestPlan.stack([c.ingest for c in configs], MEGA_GRID.dtype)
    hw = np.asarray([i.shape for i in images], np.int32)
    ref = batched_fused_overlay_step(
        MEGA_GRID, 1, stacked, ingests, jnp.asarray(canvas), hw
    )
    got = make_batched_fused_pallas_fn(MEGA_GRID, radius=1)(
        stacked, ingests, hw, jnp.asarray(canvas)
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_megakernel_batched_matches_interpreter_unaligned_batch(rng):
    """Pre-packed channel path: the pallas wrapper pads the pixel axis to a
    lane multiple internally and slices back, so lane-unaligned batches
    keep the XLA contract bitwise."""
    grid = sobel_grid()
    names = ["sobel_x", "sobel_y", "sharpen", "laplace"]
    configs = [map_app(apps.ALL_APPS[n](), grid) for n in names]
    x = rng.integers(0, 256, (len(names), grid.num_inputs, 45)).astype(np.int32)
    stacked = VCGRAConfig.stack(configs)
    ref = batched_overlay_step(grid, stacked, jnp.asarray(x))
    got = make_batched_pallas_fn(grid)(stacked, jnp.asarray(x))
    assert got.shape == ref.shape
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_megakernel_casts_frames_to_grid_dtype_like_oracle(rng):
    """Frames arriving in another dtype (float32 with fractional values on
    an int32 grid) must be cast at ingest exactly like the XLA path's
    ``form_tap_bank``, or the backends diverge in dtype AND values."""
    grid = sobel_grid()
    imgs = (rng.random((2, 6, 6)) * 256 + 0.5).astype(np.float32)
    configs = [map_app(apps.ALL_APPS[n](), grid) for n in ["sobel_x", "threshold"]]
    stacked = VCGRAConfig.stack(configs)
    ingests = IngestPlan.stack([c.ingest for c in configs], grid.dtype)
    ref = batched_fused_overlay_step(grid, 1, stacked, ingests, jnp.asarray(imgs))
    got = make_batched_fused_pallas_fn(grid, radius=1)(stacked, ingests, None,
                                                       jnp.asarray(imgs))
    assert got.dtype == ref.dtype == grid.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_megakernel_settings_are_runtime_data(rng):
    """Compile-once: swapping which app runs in which slot, and the frame
    extents the slots hold, must reuse the jitted megakernel executable
    (settings and extents are SMEM operands, not trace constants)."""
    grid = sobel_grid()
    img = rng.integers(0, 256, (2, 8, 8)).astype(np.int32)
    fn = make_batched_fused_pallas_fn(grid, radius=1)
    pair_a = [map_app(apps.ALL_APPS[n](), grid) for n in ["sobel_x", "laplace"]]
    pair_b = [map_app(apps.ALL_APPS[n](), grid) for n in ["sobel_y", "identity"]]
    hw_a = np.asarray([[8, 8], [0, 0]], np.int32)
    hw_b = np.asarray([[3, 5], [8, 6]], np.int32)
    for pair, hw in ((pair_a, hw_a), (pair_b, hw_b)):
        got = fn(
            VCGRAConfig.stack(pair),
            IngestPlan.stack([c.ingest for c in pair], grid.dtype),
            hw, jnp.asarray(img),
        )
        ref = batched_fused_overlay_step(
            grid, 1, VCGRAConfig.stack(pair),
            IngestPlan.stack([c.ingest for c in pair], grid.dtype),
            jnp.asarray(img), hw,
        )
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # The compile-once assert is the point of this test; if jax ever drops
    # the private _cache_size introspection, skip loudly rather than let
    # the test silently degrade to a plain parity check.
    sizer = getattr(fn, "_cache_size", None)
    if not callable(sizer):
        pytest.skip("this jax version has no jit _cache_size introspection")
    assert sizer() == 1


# -- extent-bounded grid steps -------------------------------------------------
#
# A step (app slot, row tile) runs the datapath only where its output rows
# hold a pixel of the slot's frame; every other output is zero.  The canvas
# has three row tiles of EXT_TILE rows and lane-padded columns; the frame
# heights sweep the tile boundaries.

EXT_H, EXT_W, EXT_TILE = 21, 140, 8
EXT_HEIGHTS = [0, 1, EXT_TILE - 1, EXT_TILE, EXT_TILE + 1, EXT_H]


@functools.lru_cache(maxsize=None)
def _extent_case(kind):
    """``(pallas, xla, settings, halo)`` of one megakernel kind over three
    app slots, each executable compiled once: extents are runtime data."""
    if kind == "fused":
        grid = sobel_grid()
        configs = [map_app(apps.ALL_APPS[n](), grid)
                   for n in ["sobel_x", "threshold", "laplace"]]
        settings = (VCGRAConfig.stack(configs),
                    IngestPlan.stack([c.ingest for c in configs], grid.dtype))
        plans = [OverlayPlan(grid=grid, batched=True, fused=True,
                             backend=b, tile_rows=EXT_TILE)
                 for b in ("pallas", "xla")]
        halo = 1
    else:
        # The last stage reads past the extent's edge, so its outputs there
        # are not zero unless the kernel zeroes them.
        spec = PipelineSpec.chain(
            chain_configs(names=["gauss3", "threshold", "sobel_x"]))
        settings = (_stage_settings([spec] * 3),)
        plans = [OverlayPlan(grid=CHAIN_GRID, batched=True,
                             pipeline=(spec,) * 3, backend=b,
                             tile_rows=EXT_TILE)
                 for b in ("pallas", "xla")]
        halo = spec.total_radius
    pallas, xla = (compile_plan(p) for p in plans)
    return pallas, xla, settings, halo


@pytest.mark.parametrize("h", EXT_HEIGHTS)
@pytest.mark.parametrize("kind", ["fused", "pipeline"])
def test_megakernel_zero_outside_extent_matches_xla(kind, h, rng):
    """Both megakernels at a frame height on each side of a tile boundary,
    a width short of the canvas, an empty (0, 0) slot and a full one: the
    whole canvas is bitwise equal to the XLA twin and zero outside every
    extent, and the host's step count is the kernel's predicate."""
    pallas, xla, settings, halo = _extent_case(kind)
    hw = np.asarray([[h, 100], [0, 0], [EXT_H, EXT_W]], np.int32)
    canvas = np.zeros((3, EXT_H, EXT_W), np.int32)
    for i, (fh, fw) in enumerate(hw):
        canvas[i, :fh, :fw] = rng.integers(0, 256, (fh, fw))
    args = (*settings, hw, jnp.asarray(canvas))
    got = np.asarray(pallas(*args))
    np.testing.assert_array_equal(got, np.asarray(xla(*args)))
    for i, (fh, fw) in enumerate(hw):
        frame = got[i].reshape(-1, EXT_H, EXT_W)
        assert not frame[:, fh:, :].any() and not frame[:, :, fw:].any()
    assert got[2].any()
    grid = pallas.plan.grid
    live = min(-(-h // EXT_TILE), 3) + 3
    assert grid_steps(grid, halo, EXT_TILE, hw, EXT_H, EXT_W) == (9, live)
