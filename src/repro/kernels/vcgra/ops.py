"""Jitted public wrappers for the VCGRA Pallas kernels.

Handles batch padding to lane-aligned blocks, image packing/unpacking, and
exposes the same (grid, config, inputs) contract as the core interpreter so
the kernel drops into the Pixie facade transparently.  Image entry points
use the fused device-side ingest (``core/ingest.py``): the stencil tap
bank + channel production run as ONE jitted function instead of ~20
host-issued shift/stack ops per frame.
"""

from __future__ import annotations

import functools
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import applications as apps
from repro.core.bitstream import VCGRAConfig
from repro.core.grid import GridSpec
from repro.core.interpreter import apply_ingest, form_tap_bank, pack_inputs
from repro.core.plan import OverlayPlan, register_executor
from repro.kernels.vcgra.vcgra_kernel import (
    _pack_settings,
    default_interpret,
    vcgra_batched,
    vcgra_conventional,
    vcgra_fused_batched,
    vcgra_pipeline_batched,
    vcgra_specialized,
)


@functools.lru_cache(maxsize=None)
def _ingest_fn(radius: int, dtype):
    """Jit-once fused frame ingest: [H, W] raw image -> [C, H*W] channels
    (tap offsets trace-time constants, plan arrays runtime settings)."""

    def ingest(tap_sel, const_vals, image):
        bank = form_tap_bank(image[None], radius, dtype)[0]
        return apply_ingest(bank, (tap_sel, const_vals))

    return jax.jit(ingest)


def _pad_batch(x: jnp.ndarray, block_n: int):
    n = x.shape[-1]
    rem = (-n) % block_n
    if rem:
        x = jnp.pad(x, ((0, 0), (0, rem)))
    return x, n


def pack_settings_batched(grid: GridSpec, stacked_configs):
    """Interpreter-style stacked settings (``VCGRAConfig.stack``: per-level
    tuples of [N, w] / [N, w, 2] plus out_sel [N, K]) -> the dense
    rectangular SMEM banks the batched megakernels prefetch:
    ``(ops int32 [N, L, max_w], sel int32 [N, L, max_w, 2], out int32 [N, K])``.
    Pad slots hold Op.NONE / select 0 and are never read (the kernel loops
    the grid's true per-level widths)."""
    opcodes, selects, out_sel = stacked_configs
    max_w = max(grid.pes_per_level)
    ops_d = jnp.stack(
        [
            jnp.pad(jnp.asarray(o, jnp.int32), ((0, 0), (0, max_w - o.shape[1])))
            for o in opcodes
        ],
        axis=1,
    )
    sel_d = jnp.stack(
        [
            jnp.pad(
                jnp.asarray(s, jnp.int32),
                ((0, 0), (0, max_w - s.shape[1]), (0, 0)),
            )
            for s in selects
        ],
        axis=1,
    )
    return ops_d, sel_d, jnp.asarray(out_sel, jnp.int32)


def _batched_fused_pallas_fn(grid: GridSpec, radius: int = 1, interpret=None,
                             tile_rows=None):
    """Unjitted batched fused-ingest *megakernel* executor (the plan
    builders return this so ``compile_plan`` applies the single outer
    jit; :func:`make_batched_fused_pallas_fn` is the jitted standalone).

    Signature twin of the XLA batched fused-ingest plan executors
    (``interpreter.batched_fused_overlay_step`` and its row-tiled twin):
    ``fn(stacked_configs, stacked_ingests, hw, images) -> ys`` with
    ``images: [N, H, W] -> ys: [N, num_outputs, H*W]``, zero outside each
    app's extent ``hw`` (int32 [N, 2], or None for the whole canvas).
    Settings, ingest plans and extents are runtime operands
    (scalar-prefetched to SMEM), so one executable per (grid, radius,
    tile_rows, N, H, W) serves every application and every frame size in
    the canvas -- the same compile-once contract as the XLA path,
    bitwise-equal outputs.  ``tile_rows`` (int / ``tiling.TILE_AUTO`` /
    None) selects the pixel-axis row tiling of the kernel grid.
    """

    def fn(stacked_configs, stacked_ingests, hw, images):
        settings = pack_settings_batched(grid, stacked_configs)
        tap_sel, const_vals = stacked_ingests
        return vcgra_fused_batched(
            grid, radius, settings,
            (jnp.asarray(tap_sel, jnp.int32), const_vals),
            images, interpret=interpret, tile_rows=tile_rows, hw=hw,
        )

    return fn


def make_batched_fused_pallas_fn(grid: GridSpec, radius: int = 1,
                                 interpret=None, tile_rows=None):
    """Jit-once standalone form of :func:`_batched_fused_pallas_fn`."""
    return jax.jit(_batched_fused_pallas_fn(grid, radius, interpret, tile_rows))


def pallas_pipeline_fn(grid: GridSpec, radii, tile_rows=None, interpret=None):
    """Unjitted pipeline-chain megakernel executor for ``compile_plan``
    (single-device pipeline plans, backend="pallas").

    Signature twin of the XLA pipeline executors:
    ``fn(stage_settings, hw, images) -> ys`` where ``stage_settings`` is a
    tuple over stages of ``(stacked_configs, stacked_ingests, out_ch)``
    exactly as the plan layer stacks them.  Each stage's interpreter-style
    settings are dense-packed (:func:`pack_settings_batched`) and stacked
    along a leading stage axis so the whole chain rides one
    scalar-prefetch bank set into :func:`vcgra_pipeline_batched`.
    """
    radii = tuple(int(r) for r in radii)

    def fn(stage_settings, hw, images):
        ops_s, sel_s, outsel_s, tap_s, const_s, outch_s = [], [], [], [], [], []
        for configs, ingests, out_ch in stage_settings:
            ops_arr, sel_arr, out_sel = pack_settings_batched(grid, configs)
            ops_s.append(ops_arr)
            sel_s.append(sel_arr)
            outsel_s.append(out_sel)
            tap_s.append(jnp.asarray(ingests[0], jnp.int32))
            const_s.append(jnp.asarray(ingests[1], grid.dtype))
            outch_s.append(jnp.asarray(out_ch, jnp.int32))
        return vcgra_pipeline_batched(
            grid, radii,
            (jnp.stack(ops_s), jnp.stack(sel_s), jnp.stack(outsel_s)),
            (jnp.stack(tap_s), jnp.stack(const_s)),
            jnp.stack(outch_s), hw, images,
            interpret=interpret, tile_rows=tile_rows,
        )

    return fn


def pallas_pipeline_stage_fn(grid: GridSpec, tile_rows=None, interpret=None):
    """Per-stage pallas executor ``stage_fn(radius, configs, ingests, hw,
    x)`` for the mesh-sharded pipeline chain drivers (``parallel/axes.py``):
    each stage runs the single-stage fused megakernel on its shard (``hw``
    the apps' extents, or None on a row band, whose rows are band-local),
    with the generic driver owning inter-stage halo exchange and masking.
    (Under shard_map the stage loop cannot fold into one kernel -- halo
    rows live on neighbor devices between stages.)"""

    def stage_fn(radius, stacked_configs, stacked_ingests, hw, images):
        return _batched_fused_pallas_fn(
            grid, int(radius), interpret, tile_rows
        )(stacked_configs, stacked_ingests, hw, images)

    return stage_fn


def _batched_pallas_fn(grid: GridSpec, block_n: Optional[int] = None,
                       interpret=None):
    """Unjitted batched (pre-packed channels) kernel executor -- the
    Pallas twin of ``interpreter.batched_overlay_step``:
    ``fn(stacked_configs, xs) -> ys`` with ``xs: [N, num_inputs, B]``.
    The kernel pads the pixel axis to whole blocks and slices it back, so
    callers keep the XLA path's contract."""

    def fn(stacked_configs, xs):
        settings = pack_settings_batched(grid, stacked_configs)
        return vcgra_batched(grid, settings, xs, block_n=block_n,
                             interpret=interpret)

    return fn


def make_batched_pallas_fn(grid: GridSpec, block_n: Optional[int] = None,
                           interpret=None):
    """Jit-once standalone form of :func:`_batched_pallas_fn`."""
    return jax.jit(_batched_pallas_fn(grid, block_n, interpret))


# -- plan executors ------------------------------------------------------------
# The kernel package registers its own cells of the OverlayPlan matrix
# (instead of being special-cased inside core/interpreter.py):
# ``compile_plan`` imports this module lazily for backend="pallas".


@register_executor("pallas", batched=True, fused=True)
def _plan_batched_fused(plan: OverlayPlan):
    return _batched_fused_pallas_fn(plan.grid, plan.radius,
                                    tile_rows=plan.tile_rows)


@register_executor("pallas", batched=True, fused=False)
def _plan_batched(plan: OverlayPlan):
    return _batched_pallas_fn(plan.grid)


def _lift_app_axis(tree):
    """Add a leading N=1 app axis to every leaf (single-app adapter)."""
    return jax.tree_util.tree_map(lambda a: a[None], tree)


@register_executor("pallas", batched=False, fused=False)
def _plan_single(plan: OverlayPlan):
    """Single-app pallas execution rides the batched kernel with N=1 (the
    megakernels are the only settings-as-runtime-data pallas path; a
    dedicated single-app kernel would re-specialize per app)."""
    batched = _batched_pallas_fn(plan.grid)

    def fn(config, x):
        return batched(_lift_app_axis(config), x[None])[0]

    return fn


@register_executor("pallas", batched=False, fused=True)
def _plan_single_fused(plan: OverlayPlan):
    batched = _batched_fused_pallas_fn(plan.grid, plan.radius,
                                       tile_rows=plan.tile_rows)

    def fn(config, ingest, image):
        return batched(_lift_app_axis(config), _lift_app_axis(ingest),
                       None, image[None])[0]

    return fn


def vcgra_apply(
    grid: GridSpec,
    config: VCGRAConfig,
    x: jnp.ndarray,
    mode: str = "specialized",
    block_n: int = 1024,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Run a mapped application over a channel-major batch [num_inputs, N].
    ``interpret=None`` auto-detects the platform (compiled on TPU,
    interpreted elsewhere)."""
    xp, n = _pad_batch(x, block_n)
    if mode == "specialized":
        fn = jax.jit(
            functools.partial(
                vcgra_specialized, grid, config, block_n=block_n, interpret=interpret
            )
        )
        y = fn(xp)
    elif mode == "conventional":
        ops_arr, sel_arr, out_sel, _ = _pack_settings(grid, config)
        fn = jax.jit(
            functools.partial(
                vcgra_conventional, grid, block_n=block_n, interpret=interpret
            )
        )
        y = fn((ops_arr, sel_arr, out_sel), xp)
    else:
        raise ValueError(f"unknown mode {mode!r}")
    return y[:, :n]


def vcgra_apply_image(
    grid: GridSpec,
    config: VCGRAConfig,
    image: jnp.ndarray,
    mode: str = "specialized",
    block_n: int = 1024,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Stencil-app convenience: [H, W] image -> [H, W] (or [K, H, W]) output.

    Takes the fused ingest path whenever the config carries an
    :class:`~repro.core.ingest.IngestPlan` (one jitted tap-bank + select
    per frame); falls back to the host-side two-step oracle otherwise.
    """
    H, W = image.shape
    if config.ingest is not None:
        plan = config.ingest
        x = _ingest_fn(plan.radius, grid.dtype)(
            *plan.to_jax(grid.dtype), jnp.asarray(image)
        )
    else:
        taps = apps.stencil_inputs(image)
        feed = {k: v for k, v in taps.items() if k in config.input_order}
        x = pack_inputs(config, feed, grid.dtype)
    y = vcgra_apply(grid, config, x, mode=mode, block_n=block_n, interpret=interpret)
    y = y.reshape((-1, H, W))
    return y[0] if y.shape[0] == 1 else y
