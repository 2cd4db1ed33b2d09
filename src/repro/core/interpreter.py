"""Conventional VCGRA execution: the compile-once overlay interpreter.

This is the software analogue of the *conventional* VCGRA implementation:
a generic datapath whose settings registers (PE opcodes, VC mux selects)
are runtime data.  The interpreter is jitted **once per grid structure**;
afterwards any application mapped on that grid runs by swapping config
arrays -- no retrace, no recompile.  That reproduces the overlay's central
claim (paper Sec. V-E): implementing a new image-processing application
costs only mapping (<1 s) + reconfiguration, not a full hardware compile
(~1200 s).

Costs faithfully mirrored from the hardware:

* every PE computes *all* functional units and muxes the result
  (``ops.apply_generic``) -- like the settings-register-driven generic PE;
* every VC routing is a gather (``jnp.take``) over all predecessor outputs
  -- like the per-port connection multiplexers;

both of which the parameterized path (``specialize.py``) folds away.
"""

from __future__ import annotations

import warnings
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import ops as pe_ops
from repro.core.bitstream import VCGRAConfig
from repro.core.grid import GridSpec
from repro.core.ingest import IngestPlan, tap_offsets

# Padding/bucketing primitives live in core/tiling.py (one source of truth
# shared with the plan compiler and the fleet scheduler); re-exported here
# because this module is their historical home.
from repro.core.tiling import (  # noqa: F401
    halo_row_slabs,
    num_row_tiles,
    pad_batches,
    pad_channels,
    resolve_tile_rows,
)

ConfigArrays = Tuple[Tuple[jnp.ndarray, ...], Tuple[jnp.ndarray, ...], jnp.ndarray]
IngestArrays = Tuple[jnp.ndarray, jnp.ndarray]  # (tap_sel, const_vals)

#: Execution backends for the batched overlay executors.  "xla" is the
#: hand-lowered jnp interpreter (the bitwise oracle); "pallas" routes the
#: same stacked settings through the batched VCGRA megakernels
#: (``repro.kernels.vcgra``), interpreted off-TPU and compiled on TPU.
BACKENDS = ("xla", "pallas")


def check_backend(backend: str) -> str:
    """Validate (and return) a backend name; shared by every layer that
    takes the backend axis (interpreter, fleet, front-end)."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of {BACKENDS}")
    return backend


def pack_inputs(
    config: VCGRAConfig,
    inputs: Dict[str, jnp.ndarray],
    dtype,
    batch_shape: Optional[Tuple[int, ...]] = None,
) -> jnp.ndarray:
    """Order named inputs into the memory-interface channel layout
    ``[num_inputs, batch]``; missing names fall back to const defaults.

    When *every* channel is const-valued the batch shape cannot be
    inferred from the inputs -- pass ``batch_shape`` explicitly, otherwise
    this raises instead of silently producing a scalar ``()`` batch.
    """
    cols = []
    for name in config.input_order:
        if name in inputs:
            v = jnp.asarray(inputs[name], dtype=dtype)
            if batch_shape is None:
                batch_shape = v.shape
            cols.append(v)
        elif name in config.const_values:
            cols.append(None)  # fill after batch shape known
        else:
            raise KeyError(f"missing input {name!r}")
    if batch_shape is None:
        raise ValueError(
            f"every channel of {config.app_name!r} is const-valued, so the "
            "pixel batch shape cannot be inferred; pass batch_shape= "
            "explicitly (e.g. batch_shape=(n,))"
        )
    cols = [
        jnp.full(batch_shape, config.const_values[name], dtype=dtype)
        if c is None
        else jnp.broadcast_to(c, batch_shape)
        for c, name in zip(cols, config.input_order)
    ]
    return jnp.stack(cols, axis=0)


def overlay_step(
    grid: GridSpec, config: ConfigArrays, x: jnp.ndarray
) -> jnp.ndarray:
    """One full pass of the batch through the PE-level pipeline.

    ``x``: [num_inputs, batch] channel values at the top memory VC.
    The loop over levels is a *Python* loop: the grid structure is static
    (it is the overlay), only the settings are traced arrays.
    """
    opcodes, selects, out_sel = config
    assert len(opcodes) == grid.num_levels
    for lvl in range(grid.num_levels):
        # VC above level `lvl`: one mux per PE input port.
        a = jnp.take(x, selects[lvl][:, 0], axis=0)
        b = jnp.take(x, selects[lvl][:, 1], axis=0)
        # Generic PE: all functional units + output mux.
        x = pe_ops.apply_generic(opcodes[lvl], a, b)
    # Bottom memory-interface VC.
    return jnp.take(x, out_sel, axis=0)


def _deprecated_factory(name: str, plan) -> "object":
    """Shared body of the legacy ``make_*_overlay_fn`` shims: warn, then
    delegate to the unified plan pipeline.  The returned
    ``OverlayExecutable`` is callable with the exact legacy signature and
    bitwise-identical (it wraps the very same step function)."""
    from repro.core.plan import compile_plan

    warnings.warn(
        f"{name} is deprecated; build an OverlayPlan and call "
        "repro.core.plan.compile_plan(plan) instead (one entrypoint for "
        "the whole fusion x batching x backend x devices matrix)",
        DeprecationWarning,
        stacklevel=3,
    )
    return compile_plan(plan)


def make_overlay_fn(grid: GridSpec):
    """Deprecated: use ``compile_plan(OverlayPlan(grid=grid))``.

    Returns ``fn(config_arrays, x) -> y`` with
    ``x: [num_inputs, batch] -> y: [num_outputs, batch]``.
    Different applications = different `config_arrays` of identical shapes
    => a single XLA executable serves them all.
    """
    from repro.core.plan import OverlayPlan

    return _deprecated_factory("make_overlay_fn", OverlayPlan(grid=grid))


def batched_overlay_step(
    grid: GridSpec, configs: ConfigArrays, xs: jnp.ndarray
) -> jnp.ndarray:
    """N applications through one overlay in a single dispatch.

    ``configs``: stacked settings (``VCGRAConfig.stack``), leaves carrying a
    leading app axis N; ``xs``: [N, num_inputs, batch].  Semantically this
    is ``jax.vmap(overlay_step)`` over the app axis -- the software
    analogue of N tenant bitstreams resident in one physical overlay -- but
    the VC muxes are lowered by hand: per-app selects are offset into one
    flat [N*rows, batch] value bank so each level is a single plain gather
    (identical to the sequential path's ``jnp.take``), not a
    batched-indices gather, which XLA:CPU lowers an order of magnitude
    slower.
    """
    opcodes, selects, out_sel = configs
    assert len(opcodes) == grid.num_levels
    n = xs.shape[0]
    x = xs
    for lvl in range(grid.num_levels):
        rows = x.shape[1]
        flat = x.reshape((n * rows,) + x.shape[2:])
        offs = (jnp.arange(n, dtype=selects[lvl].dtype) * rows)[:, None]
        a = jnp.take(flat, (selects[lvl][:, :, 0] + offs).reshape(-1), axis=0)
        b = jnp.take(flat, (selects[lvl][:, :, 1] + offs).reshape(-1), axis=0)
        shape = (n, -1) + x.shape[2:]
        x = pe_ops.apply_generic(opcodes[lvl], a.reshape(shape), b.reshape(shape))
    rows = x.shape[1]
    flat = x.reshape((n * rows,) + x.shape[2:])
    offs = (jnp.arange(n, dtype=out_sel.dtype) * rows)[:, None]
    y = jnp.take(flat, (out_sel + offs).reshape(-1), axis=0)
    return y.reshape((n, -1) + x.shape[2:])


def make_batched_overlay_fn(grid: GridSpec, backend: str = "xla"):
    """Deprecated: use ``compile_plan(OverlayPlan(grid=grid, batched=True,
    backend=backend))``.

    Returns ``fn(stacked_configs, xs) -> ys`` with
    ``xs: [N, num_inputs, batch] -> ys: [N, num_outputs, batch]``.
    Like :func:`make_overlay_fn` the executable depends only on the grid
    structure and the (N, batch) shape -- any N applications mapped on the
    grid share it, so a fleet scheduler that pads to fixed (N, batch) tiles
    compiles exactly once per (grid, backend).
    """
    from repro.core.plan import OverlayPlan

    return _deprecated_factory(
        "make_batched_overlay_fn",
        OverlayPlan(grid=grid, batched=True, backend=backend),
    )


# -- fused device-side ingest (line buffers inside the dispatch) --------------


def form_tap_bank(images: jnp.ndarray, radius: int, dtype) -> jnp.ndarray:
    """Line-buffer formation: raw frames -> the stencil tap bank.

    ``images``: [N, H, W] -> bank [N, T+1, H*W] where row ``t`` holds tap
    ``tap_offsets(radius)[t]`` (zero-padded shift, exactly
    ``applications.stencil_inputs``) and the trailing row is zeros (the
    const/padding producer).  The offsets are trace-time constants, so each
    tap is a *static* slice of one padded buffer -- the whole bank lowers
    to cheap views, no batched-indices gather (see DESIGN.md).
    """
    imgs = jnp.asarray(images, dtype)
    n, H, W = imgs.shape
    r = radius
    padded = jnp.pad(imgs, ((0, 0), (r, r), (r, r)))
    rows = [
        padded[:, r + dj : r + dj + H, r + di : r + di + W].reshape(n, H * W)
        for dj, di in tap_offsets(radius)
    ]
    rows.append(jnp.zeros((n, H * W), dtype))
    return jnp.stack(rows, axis=1)


def form_tap_bank_slab(slabs: jnp.ndarray, radius: int, dtype) -> jnp.ndarray:
    """Line-buffer formation for one row tile: a row-haloed slab -> bank.

    ``slabs``: [N, tile_rows + 2*radius, W] where the first and last
    ``radius`` rows are the halo (real neighbour rows mid-frame, zeros at
    the frame border -- the caller slices them from the zero-row-padded
    frame).  Returns [N, T+1, tile_rows*W]: rows are only *column*-padded
    here because the row halo already travels with the slab; every bank row
    is bitwise the ``form_tap_bank`` row restricted to the tile's pixels.
    """
    s = jnp.asarray(slabs, dtype)
    n, S, W = s.shape
    r = radius
    tr = S - 2 * r
    padded = jnp.pad(s, ((0, 0), (0, 0), (r, r)))
    rows = [
        padded[:, r + dj : r + dj + tr, r + di : r + di + W].reshape(n, tr * W)
        for dj, di in tap_offsets(radius)
    ]
    rows.append(jnp.zeros((n, tr * W), dtype))
    return jnp.stack(rows, axis=1)


def apply_ingest(bank: jnp.ndarray, ingest: IngestArrays) -> jnp.ndarray:
    """Produce the memory-VC channels of ONE app from its tap bank.

    ``bank``: [T+1, pixels]; ``ingest``: (tap_sel [C], const_vals [C]).
    Channels selecting the zero row take their const value verbatim (0 for
    grid-padding channels), so the result needs no further ``pad_channels``.
    """
    tap_sel, const_vals = ingest
    zero_row = bank.shape[0] - 1
    gathered = jnp.take(bank, tap_sel, axis=0)
    return jnp.where((tap_sel == zero_row)[:, None], const_vals[:, None], gathered)


def fused_overlay_step(
    grid: GridSpec, radius: int, config: ConfigArrays,
    ingest: IngestArrays, image: jnp.ndarray,
) -> jnp.ndarray:
    """pack + dispatch fused: one raw [H, W] frame -> [num_outputs, H*W]
    inside a single executable.  The ingest arrays are runtime settings
    (like the VC mux selects), so any app mapped on the grid reuses the
    same compiled function."""
    bank = form_tap_bank(image[None], radius, grid.dtype)[0]
    x = apply_ingest(bank, ingest)
    return overlay_step(grid, config, x)


def make_fused_overlay_fn(grid: GridSpec, radius: int = 1):
    """Deprecated: use ``compile_plan(OverlayPlan(grid=grid, fused=True,
    radius=radius))``.

    Returns ``fn(config_arrays, ingest_arrays, image) -> y`` with
    ``image: [H, W] -> y: [num_outputs, H*W]``.  Like
    :func:`make_overlay_fn` the executable depends only on the grid
    structure (plus the stencil radius and frame shape): tap offsets are
    trace-time constants, tap *selection* is runtime data."""
    from repro.core.plan import OverlayPlan

    return _deprecated_factory(
        "make_fused_overlay_fn",
        OverlayPlan(grid=grid, fused=True, radius=radius),
    )


def batched_fused_overlay_step(
    grid: GridSpec, radius: int, configs: ConfigArrays,
    ingests: IngestArrays, images: jnp.ndarray, hw=None,
) -> jnp.ndarray:
    """N apps on N raw frames in ONE dispatch, line buffers included.

    ``images``: [N, H, W]; ``ingests``: stacked plan arrays
    (``IngestPlan.stack``), tap_sel [N, C] / const_vals [N, C].  The
    per-app tap selection uses the same flat-bank offset trick as the VC
    muxes in :func:`batched_overlay_step`: one plain gather over a
    [N*(T+1), pixels] bank, never a batched-indices gather.  ``hw``
    (int32 [N, 2] true frame extents, or None for the whole canvas)
    zeroes the outputs outside each app's extent, as the megakernel does.
    """
    bank = form_tap_bank(images, radius, grid.dtype)
    ys = batched_overlay_step(grid, configs, select_channels_batched(bank, ingests))
    return mask_outputs(ys, hw, *images.shape[1:])


def select_channels_batched(bank: jnp.ndarray, ingests: IngestArrays) -> jnp.ndarray:
    """Produce every app's memory-VC channels from a batched tap bank
    [N, T+1, pixels] -- the flat-bank offset gather shared by the untiled
    and row-tiled fused executors."""
    tap_sel, const_vals = ingests
    n, t1, pixels = bank.shape
    flat = bank.reshape(n * t1, pixels)
    offs = (jnp.arange(n, dtype=tap_sel.dtype) * t1)[:, None]
    gathered = jnp.take(flat, (tap_sel + offs).reshape(-1), axis=0)
    gathered = gathered.reshape(n, -1, pixels)
    return jnp.where((tap_sel == t1 - 1)[..., None], const_vals[..., None], gathered)


def tiled_batched_fused_overlay_step(
    grid: GridSpec, radius: int, tile_rows, configs: ConfigArrays,
    ingests: IngestArrays, images: jnp.ndarray, hw=None,
) -> jnp.ndarray:
    """Row-tiled twin of :func:`batched_fused_overlay_step`: bitwise-equal
    outputs with the tap bank formed per ``[tile_rows + 2*radius, W]``
    slab -- the XLA *oracle* for the tiled Pallas megakernel.  Note that
    only the Pallas grid actually bounds residency (one slab in VMEM at a
    time); this twin trades peak memory for fusion (all slabs, the full
    bank and T-replicated settings live at once -- slightly *more* than
    untiled), which is the right trade for the oracle/CPU role it plays.

    ``tile_rows``: rows per tile, ``tiling.TILE_AUTO`` (VMEM budget
    heuristic) or an int; resolved against the static frame shape at trace
    time, so compile-once per (grid, radius, N, H, W) still holds.  The
    frame's row axis is zero-padded up to ``T * tile_rows`` -- the padding
    is read only as bottom halo (exactly ``form_tap_bank``'s zero border)
    and the padded output rows are sliced back off, so any ``tile_rows``,
    including ones that do not divide H, is exact.

    Lowering note: the T row tiles ride the *app* axis (every operand
    replicated/tiled to N*T leading rows) rather than a Python loop over
    tiles -- one pipeline pass over all slabs keeps XLA:CPU's fusion
    intact, where a per-tile loop fragments the program into T small op
    islands (~25% slower at smoke sizes).  The per-(app, tile) grid loop
    lives in the Pallas megakernel, where it is the whole point (VMEM
    residency); here the tile axis is just more embarrassing parallelism.
    """
    imgs = jnp.asarray(images, grid.dtype)
    n, H, W = imgs.shape
    r = radius
    tr = resolve_tile_rows(tile_rows, H, W, r, grid)
    if tr >= H:
        return batched_fused_overlay_step(grid, radius, configs, ingests, imgs,
                                          hw)
    T = num_row_tiles(H, tr)
    slabs = halo_row_slabs(imgs, tr, r).reshape(n * T, tr + 2 * r, W)
    bank = form_tap_bank_slab(slabs, radius, grid.dtype)   # [N*T, taps+1, tr*W]
    rep = partial(jnp.repeat, repeats=T, axis=0)
    xs = select_channels_batched(bank, jax.tree_util.tree_map(rep, ingests))
    ys = batched_overlay_step(grid, jax.tree_util.tree_map(rep, configs), xs)
    # [N*T, K, tr*W] -> per-app tile concat along the pixel axis (row-major
    # flattening makes each tile's pixels contiguous), minus the pad rows.
    y = ys.reshape(n, T, -1, tr * W).swapaxes(1, 2).reshape(n, -1, T * tr * W)
    return mask_outputs(y[:, :, : H * W], hw, H, W)


def valid_pixel_mask(hw: jnp.ndarray, H: int, W: int) -> jnp.ndarray:
    """``[N, H, W]`` bool mask of each app's true frame region inside a
    padded canvas: ``hw`` is int32 ``[N, 2]`` of per-app ``(rows, cols)``.

    The pipeline executors zero everything outside it between stages: a
    stage's output on canvas padding is NOT zero (its taps read real frame
    pixels), but the next stage's border must read zeros -- exactly what
    the staged oracle sees when each intermediate is re-embedded into a
    fresh zero canvas.  Masking is what keeps the fused chain bitwise
    equal to the per-stage dispatch sequence on bucketed canvases.
    """
    hw = jnp.asarray(hw, jnp.int32)
    rows_in = jnp.arange(H, dtype=jnp.int32)[None, :, None] < hw[:, 0][:, None, None]
    cols_in = jnp.arange(W, dtype=jnp.int32)[None, None, :] < hw[:, 1][:, None, None]
    return jnp.logical_and(rows_in, cols_in)


def mask_outputs(ys: jnp.ndarray, hw, H: int, W: int) -> jnp.ndarray:
    """``ys [N, K, H*W]`` with every pixel outside each app's extent
    ``hw`` set to zero (the executors' output contract); ``hw=None`` is
    the whole canvas and leaves ``ys`` as it is."""
    if hw is None:
        return ys
    n = ys.shape[0]
    valid = valid_pixel_mask(hw, H, W).reshape(n, 1, H * W)
    return jnp.where(valid, ys, jnp.zeros_like(ys))


def forward_stage_output(ys: jnp.ndarray, out_ch: jnp.ndarray, H: int,
                         W: int) -> jnp.ndarray:
    """Select each app's forwarded output channel from a stage's
    ``[N, K, H*W]`` result, already zero outside the app's true frame
    region, as the next stage's ``[N, H, W]`` frames: the inter-stage hop
    of the operand-settings pipeline chain.  ``out_ch`` is int32 ``[N]``
    (runtime data, like every other setting)."""
    y = jnp.take_along_axis(
        ys, out_ch.astype(jnp.int32)[:, None, None], axis=1
    )[:, 0]
    return y.reshape(-1, H, W)


def pipeline_batched_fused_step(
    grid: GridSpec, radii, stage_fn, stage_settings, hw, images,
) -> jnp.ndarray:
    """Operand-settings pipeline chain: N per-app stage chains on N raw
    frames in ONE dispatch, every intermediate staying a device-resident
    ``[N, H, W]`` frame.

    ``radii`` are the trace-time per-stage tap radii (executable shape);
    ``stage_settings`` is runtime data -- one ``(stacked_configs,
    stacked_ingests, out_ch)`` triple per stage, leaves carrying the
    leading app axis N -- so this variant shard_maps over an app/rows mesh
    (SPMD traces once; per-shard constants are impossible there).  The
    single-device XLA path instead bakes the chain at trace time
    (``plan._pipeline_specialized_fn``); both are bitwise equal to the
    staged per-stage oracle.  ``stage_fn(radius, configs, ingests, hw,
    x)`` runs one stage (the plan supplies the backend's batched fused
    step, tiled or not); the last stage returns its full ``[N, K, H*W]``
    output, zero outside ``hw`` -- its ``out_ch`` entry is forwarding
    metadata with nothing to feed.
    """
    x = jnp.asarray(images, grid.dtype)
    _, H, W = x.shape
    ys = None
    for si, r in enumerate(radii):
        configs, ingests, out_ch = stage_settings[si]
        ys = stage_fn(r, configs, ingests, hw, x)
        if si < len(radii) - 1:
            x = forward_stage_output(ys, out_ch, H, W)
    return ys


def make_batched_fused_overlay_fn(grid: GridSpec, radius: int = 1,
                                  backend: str = "xla"):
    """Deprecated: use ``compile_plan(OverlayPlan(grid=grid, batched=True,
    fused=True, radius=radius, backend=backend))``.

    Returns ``fn(stacked_configs, stacked_ingests, hw, images) -> ys`` with
    ``images: [N, H, W] -> ys: [N, num_outputs, H*W]``, zero outside each
    app's extent ``hw`` (int32 [N, 2]; None = whole canvas).  One executable
    per (grid, radius, backend, N, H, W); a fleet that pads N and the
    frame canvas to fixed tiles compiles exactly once per grid."""
    from repro.core.plan import OverlayPlan

    return _deprecated_factory(
        "make_batched_fused_overlay_fn",
        OverlayPlan(grid=grid, batched=True, fused=True, radius=radius,
                    backend=backend),
    )


def run_app_fused(
    grid: GridSpec,
    config: VCGRAConfig,
    image: jnp.ndarray,
    fused_fn=None,
) -> jnp.ndarray:
    """Convenience one-shot fused execution: raw frame in, [num_outputs,
    H*W] out.  Requires ``config.ingest`` (set by ``assemble`` whenever the
    app is image-feedable)."""
    if config.ingest is None:
        raise ValueError(
            f"app {config.app_name!r} has no ingest plan (a channel is "
            "neither a stencil tap nor a const); use the named-channel path"
        )
    if fused_fn is None:
        from repro.core.plan import OverlayPlan, compile_plan

        fused_fn = compile_plan(
            OverlayPlan(grid=grid, fused=True, radius=config.ingest.radius)
        )
    return fused_fn(
        config.to_jax(), config.ingest.to_jax(grid.dtype), jnp.asarray(image)
    )


def stack_for_dispatch(configs, xs, batch_pad=None):
    """Pad-and-stack step of a multi-tenant dispatch (`Pixie.run_many`):
    zero-pad ragged pixel batches to one length, stack configs and inputs
    along the app axis.  `runtime.fleet.PixieFleet.flush` shares the same
    primitives (`pad_batches` + `VCGRAConfig.stack`) but routes the config
    stack through its cross-flush bank cache instead of calling this.

    Returns ``(stacked_configs, xstack, batches)`` where ``batches`` are
    the original per-app batch lengths for slicing the outputs back.
    """
    batches = [x.shape[-1] for x in xs]
    pad_to = batch_pad if batch_pad is not None else max(batches)
    if pad_to < max(batches):
        raise ValueError(f"batch_pad={pad_to} < largest request {max(batches)}")
    return VCGRAConfig.stack(configs), jnp.stack(pad_batches(xs, pad_to)), batches


def run_app(
    grid: GridSpec,
    config: VCGRAConfig,
    inputs: Dict[str, jnp.ndarray],
    overlay_fn=None,
) -> Dict[int, jnp.ndarray]:
    """Convenience one-shot execution (packs inputs, runs, unpacks)."""
    dtype = grid.dtype
    if overlay_fn is None:
        from repro.core.plan import OverlayPlan, compile_plan

        overlay_fn = compile_plan(OverlayPlan(grid=grid))
    fn = overlay_fn
    x = pack_inputs(config, inputs, dtype)
    y = fn(config.to_jax(), x)
    return {k: y[k] for k in range(y.shape[0])}
