"""Pallas TPU kernel: the VCGRA grid executor.

TPU-native adaptation of the Pixie pipeline (see DESIGN.md): the pixel
stream is tiled HBM -> VMEM in lane-aligned blocks, and the PE-level
pipeline of the overlay executes per tile entirely in VMEM/VREGs.  Two
variants mirror the paper's two implementations:

* **specialized** (parameterized configuration): the settings are trace-
  time constants; each PE emits exactly its configured functional unit and
  every VC mux folds into direct SSA wiring.  This is the TLUT/TCON
  analogue and the fast path.

* **conventional**: the settings live in SMEM (scalar-prefetched, the
  settings-register analogue); each PE branches on its scalar opcode and
  runs only its configured functional unit, and routing is performed
  with dynamic row selects against the previous level's VMEM value
  matrix.  Same executable serves every application mapped on the grid.
  The paper's Table I resource cost of a generic PE is modelled by
  ``core/specialize.py`` and ``core/synthesis.py``, not by how this kernel
  spends cycles; the XLA interpreter keeps the per-lane mux form
  (``ops.apply_generic``), where the opcode is a vector.

Block layout: inputs are stacked channel-major ``[num_inputs, N]`` where N
is the flattened pixel batch; blocks are ``(num_inputs, block_n)`` with
``block_n`` a multiple of 128 (lane width).  The level pipeline is fully
unrolled inside the kernel: VMEM working set is
``O(max_level_width * block_n)`` elements.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ops as pe_ops
from repro.core.bitstream import VCGRAConfig
from repro.core.grid import GridSpec
from repro.core.ingest import tap_offsets
from repro.core.ops import Op
from repro.core.specialize import _live_slots

# LANE and SUBLANE are defined in core/tiling.py (the tile-height
# resolver and the kernel must agree on them); LANE is re-exported here,
# its historical home, for the callers that import it from the kernel
# package.
from repro.core.tiling import (  # noqa: F401
    LANE, SUBLANE, num_row_tiles, resolve_tile_rows,
)


def default_interpret() -> bool:
    """Pallas interpret-mode default: compiled on a real TPU, interpreted
    everywhere else (CPU/GPU CI).  Callers can always override."""
    return jax.default_backend() != "tpu"


def _resolve_interpret(interpret: Optional[bool]) -> bool:
    return default_interpret() if interpret is None else bool(interpret)


# -- specialized kernel --------------------------------------------------------


def _specialized_body(grid: GridSpec, config: VCGRAConfig, x_ref, o_ref):
    """Kernel body with config burned in: a pure unrolled dataflow pipeline."""
    x = x_ref[...]
    dtype = x.dtype
    live = _live_slots(grid, config)
    const_idx = {}
    prev = {}
    for lvl in range(grid.num_levels):
        cur = {}
        for slot in sorted(live[lvl]):
            op = Op(int(config.opcodes[lvl][slot]))
            if op == Op.NONE:
                cur[slot] = jnp.zeros(x.shape[1:], dtype)
                continue
            sa = int(config.selects[lvl][slot, 0])
            sb = int(config.selects[lvl][slot, 1])
            a = x[sa] if lvl == 0 else prev[sa]
            b = a if op in pe_ops.UNARY_OPS else (x[sb] if lvl == 0 else prev[sb])
            cur[slot] = pe_ops.apply_op(op, a, b)
        prev = cur
    rows = [prev[int(s)] for s in config.out_sel]
    o_ref[...] = jnp.stack(rows, axis=0)


def vcgra_specialized(
    grid: GridSpec,
    config: VCGRAConfig,
    x: jnp.ndarray,
    block_n: int = 1024,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Specialized-path pallas executor.  x: [num_inputs, N] (N % block_n == 0).

    ``interpret=None`` auto-detects the platform (compiled on TPU,
    interpreted elsewhere)."""
    interpret = _resolve_interpret(interpret)
    n_in, n = x.shape
    assert n % block_n == 0, f"N={n} not a multiple of block_n={block_n}"
    assert block_n % LANE == 0, f"block_n must be lane-aligned (x{LANE})"
    body = functools.partial(_specialized_body, grid, config)
    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((grid.num_outputs, n), x.dtype),
        grid=(n // block_n,),
        in_specs=[pl.BlockSpec((n_in, block_n), lambda i: (0, i))],
        out_specs=pl.BlockSpec((grid.num_outputs, block_n), lambda i: (0, i)),
        interpret=interpret,
        name="vcgra_specialized",
    )(x)


# -- conventional kernels --------------------------------------------------------
#
# Every conventional body runs the PE-level pipeline through VMEM refs
# with a leading value axis: a PE's routing select is a dynamic index on
# that untiled axis (an address offset, the VC mux analogue), and its
# opcode is an SMEM scalar.  Values are ``[rows, lanes]`` pixel blocks,
# never flattened, so every load, store and elementwise op keeps the
# (sublane, lane) tiling the TPU compiler requires.  Settings arrays are
# scalar-prefetched FLAT (SMEM pads the trailing dimensions of a
# multi-dimensional operand to whole tiles, which overflows SMEM for
# stage-stacked banks) and read through :class:`_Smem`.


class _Smem:
    """Row-major multi-index view of a flattened SMEM operand."""

    def __init__(self, ref, shape: Tuple[int, ...]):
        self._ref = ref
        strides, acc = [], 1
        for d in reversed(shape):
            strides.append(acc)
            acc *= int(d)
        self._strides = tuple(reversed(strides))

    def __getitem__(self, idx):
        idx = idx if isinstance(idx, tuple) else (idx,)
        off = 0
        for i, stride in zip(idx, self._strides):
            off = off + i * stride
        return self._ref[off]


def _smem_operands(*arrays):
    """Flatten scalar-prefetch operands; returns ``(flat, shapes)``.  Const
    values ride as 32-bit words (SMEM holds 32-bit scalars; 16-bit grid
    values widen and narrow back exactly)."""
    flat, shapes = [], []
    for a in arrays:
        a = jnp.asarray(a)
        if a.dtype.itemsize != 4:
            a = a.astype(jnp.int32 if jnp.issubdtype(a.dtype, jnp.integer)
                         else jnp.float32)
        flat.append(a.reshape(-1))
        shapes.append(tuple(a.shape))
    return flat, tuple(shapes)


def _clamp(idx, size: int):
    """Clamp a runtime select into ``[0, size)`` (``dynamic_slice``'s
    out-of-range rule; VMEM reads are not bounds-checked on the chip)."""
    return jnp.minimum(jnp.maximum(idx, 0), size - 1)


#: The functional units a PE can be configured to, ADD through ABS.
_UNITS = tuple(Op(k) for k in range(Op.ADD, Op.ABS + 1))


def _pe_unit(op, a, b, out_ref) -> None:
    """One PE: store the unit its SMEM opcode ``op`` names into ``out_ref``.

    The opcode is a scalar, so each unit sits behind its own scalar branch
    and only the configured one runs; ``apply_op`` holds every unit's
    semantics.  NONE, MAC and any out-of-range code store zeros, as
    ``ops.apply_generic`` (the XLA interpreter's per-lane mux) does.
    """
    for unit in _UNITS:
        @pl.when(op == int(unit))
        def _(unit=unit):
            out_ref[...] = pe_ops.apply_op(unit, a, b)

    @pl.when(jnp.logical_or(op < int(Op.ADD), op > int(Op.ABS)))
    def _():
        out_ref[...] = jnp.zeros_like(a)


def _run_levels(grid: GridSpec, idx: Tuple, op_ref, sel_ref, src_ref, lvl_ref):
    """The conventional PE-level pipeline over one pixel block, shared by
    every conventional kernel body.

    ``idx`` prefixes every SMEM read (``(i,)`` for a batched bank with a
    leading app axis, ``(si, i)`` for a stage-stacked one).  ``src_ref``
    holds the memory-VC channels ``[C, rows, lanes]``; ``lvl_ref`` is the
    ``[2, max_w, rows, lanes]`` ping-pong buffer the levels write in
    turn.  Each PE branches on its scalar opcode and computes only its
    configured unit (:func:`_pe_unit`), so a level costs the units its
    app uses, not every unit of every PE.  Returns the ref view holding
    the last level's outputs.  Dense settings are padded to max_w but
    only the grid's true per-level width is ever read, so pad slots cost
    nothing.
    """
    src, n_src = src_ref, grid.num_inputs
    for lvl in range(grid.num_levels):    # grid structure static, settings not
        dst = lvl_ref.at[lvl % 2]

        def pe(slot, carry, lvl=lvl, src=src, dst=dst, n_src=n_src):
            a = src[_clamp(sel_ref[idx + (lvl, slot, 0)], n_src)]
            b = src[_clamp(sel_ref[idx + (lvl, slot, 1)], n_src)]
            _pe_unit(op_ref[idx + (lvl, slot)], a, b, dst.at[slot])
            return carry

        jax.lax.fori_loop(0, grid.pes_per_level[lvl], pe, 0)
        src, n_src = dst, grid.pes_per_level[lvl]
    return src


def _output(grid: GridSpec, idx: Tuple, outsel_ref, last_ref, k):
    """Output VC ``k``: the last-level row its select names."""
    return last_ref[_clamp(outsel_ref[idx + (k,)], grid.pes_per_level[-1])]


def _pack_settings(grid: GridSpec, config: VCGRAConfig):
    max_w = max(grid.pes_per_level)
    ops_arr = np.zeros((grid.num_levels, max_w), np.int32)
    sel_arr = np.zeros((grid.num_levels, max_w, 2), np.int32)
    for lvl in range(grid.num_levels):
        w = grid.pes_per_level[lvl]
        ops_arr[lvl, :w] = config.opcodes[lvl]
        sel_arr[lvl, :w] = config.selects[lvl]
    return jnp.asarray(ops_arr), jnp.asarray(sel_arr), jnp.asarray(config.out_sel), max_w


def vcgra_conventional(
    grid: GridSpec,
    config_arrays: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],
    x: jnp.ndarray,
    block_n: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Conventional-path pallas executor: one executable per *grid*, any
    application's packed settings arrays accepted at runtime.  It is the
    batched kernel with one app.  ``interpret=None`` auto-detects the
    platform."""
    settings = tuple(jnp.asarray(a)[None] for a in config_arrays)
    return vcgra_batched(grid, settings, x[None], block_n=block_n,
                         interpret=interpret)[0]


# -- batched megakernels -------------------------------------------------------
#
# The multi-tenant twins of the interpreter's batched paths
# (``interpreter.batched_overlay_step`` / ``batched_fused_overlay_step``):
# ONE pallas_call whose grid iterates the app axis, with every tenant's
# settings bank (PE opcodes, VC mux selects, output selects -- and for the
# fused variant the ingest plan's tap selects and const values)
# scalar-prefetched into SMEM.  The kernel instance for app ``i`` indexes
# its own settings rows with ``pl.program_id(0)``, so N different
# applications execute through one compiled kernel -- the
# settings-register analogue at fleet scale.


def _batched_body(grid: GridSpec, shapes, *refs):
    """One app per grid step over pre-packed channels
    ``[1, C, rows, LANE]`` (the pixel axis folded onto sublanes x lanes)."""
    op_ref, sel_ref, outsel_ref = (_Smem(r, s) for r, s in zip(refs, shapes))
    x_ref, o_ref, lvl_ref = refs[3:]
    i = pl.program_id(0)
    last = _run_levels(grid, (i,), op_ref, sel_ref, x_ref.at[0], lvl_ref)
    for k in range(grid.num_outputs):
        o_ref[0, k] = _output(grid, (i,), outsel_ref, last, k)


def vcgra_batched(
    grid: GridSpec,
    settings: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],
    x: jnp.ndarray,
    block_n: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Batched conventional executor: N tenants in ONE pallas_call.

    ``settings``: dense-packed banks (ops [N, L, max_w], sel [N, L, max_w, 2],
    out_sel [N, K]) -- see ``ops.pack_settings_batched``.
    ``x``: [N, num_inputs, B]; returns [N, num_outputs, B].  The pixel axis
    is zero-padded to whole blocks and folded to ``[N, C, B/LANE, LANE]``
    so every value is a 2-D sublane x lane tile; the padding is sliced
    back off.  ``block_n`` (pixels per block, a LANE multiple, default
    one sublane tile of lanes) is rounded up to whole sublane tiles
    (``SUBLANE * LANE`` pixels) or the whole padded axis, whichever is
    smaller -- the TPU compiler's block rule.
    """
    interpret = _resolve_interpret(interpret)
    n_apps, n_in, b = x.shape
    assert block_n is None or block_n % LANE == 0, (
        f"block_n must be lane-aligned (x{LANE})")
    lane_rows = -(-b // LANE)
    br = min(_round_up((block_n or LANE) // LANE, SUBLANE), lane_rows)
    n_rows = _round_up(lane_rows, br)
    xp = jnp.pad(x, ((0, 0), (0, 0), (0, n_rows * LANE - b)))
    xp = xp.reshape(n_apps, n_in, n_rows, LANE)
    K = grid.num_outputs
    smem, shapes = _smem_operands(*settings)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(smem),
        grid=(n_apps, n_rows // br),
        in_specs=[pl.BlockSpec((1, n_in, br, LANE),
                               lambda i, j, *_: (i, 0, j, 0))],
        out_specs=pl.BlockSpec((1, K, br, LANE),
                               lambda i, j, *_: (i, 0, j, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, max(grid.pes_per_level), br, LANE), x.dtype),
        ],
    )
    y = pl.pallas_call(
        functools.partial(_batched_body, grid, shapes),
        out_shape=jax.ShapeDtypeStruct((n_apps, K, n_rows, LANE), x.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="vcgra_batched",
    )(*smem, xp)
    return y.reshape(n_apps, K, n_rows * LANE)[:, :, :b]


def _slab_dma_pipeline(frames_ref, slabs_ref, dma_sems_ref, tile_rows: int):
    """The in-kernel double-buffered HBM->VMEM slab stream shared by the
    fused and pipeline megakernels.  Starts the NEXT grid step's
    ``[slab_rows, W]`` window into the other buffer, blocks on this
    step's own, and returns the slot holding it.

    The buffer slot rotates on the LINEARIZED step index ``i*T + t``
    (rotating on the tile index alone desynchronizes producer and consumer
    at app boundaries whenever T is odd).  Next step's (app, tile) wraps
    the tile axis, so the app boundary prefetches tile 0 of app i+1.
    """
    i = pl.program_id(0)
    t = pl.program_id(1)
    n_tiles = pl.num_programs(1)
    step = i * n_tiles + t
    slot = jax.lax.rem(step, 2)
    slab_rows = slabs_ref.shape[1]

    def slab_dma(slot, app, tile):
        return pltpu.make_async_copy(
            frames_ref.at[app, pl.ds(tile * tile_rows, slab_rows), :],
            slabs_ref.at[slot],
            dma_sems_ref.at[slot],
        )

    @pl.when(step == 0)
    def _():
        slab_dma(0, 0, 0).start()        # warm-up: first window, slot 0

    next_t = jax.lax.rem(t + 1, n_tiles)
    next_i = i + jax.lax.div(t + 1, n_tiles)

    @pl.when(step + 1 < pl.num_programs(0) * n_tiles)
    def _():
        slab_dma(1 - slot, next_i, next_t).start()

    slab_dma(slot, i, t).wait()
    return slot


def _form_bank(radius: int, rows: int, x_ref, bank_ref) -> None:
    """Line-buffer formation: the tap bank of ``rows`` output rows from
    the haloed input rows ``x_ref [>= rows + 2*radius, W]``.

    Tap ``(dj, di)`` is the row window starting at ``radius + dj`` (a
    static sublane offset) shifted by ``di`` columns with a lane rotate
    whose wrapped-around columns are zeroed -- exactly
    ``form_tap_bank``'s zero border (the column pad of the compiled
    layout is zeros too).
    """
    W = x_ref.shape[-1]
    col = jax.lax.broadcasted_iota(jnp.int32, (rows, W), 1)
    for t, (dj, di) in enumerate(tap_offsets(radius)):
        v = x_ref[pl.ds(radius + dj, rows), :]
        if di:
            v = pltpu.roll(v, (-di) % W, 1)
            ok = jnp.logical_and(col + di >= 0, col + di < W)
            v = jnp.where(ok, v, jnp.zeros_like(v))
        bank_ref[t, pl.ds(0, rows), :] = v


def _blocks(W: int):
    """The ``[row, lane]`` pixel block the datapath loop walks: one
    sublane tile by up to 512 lanes (a few vregs per value, so the PE
    datapath stays in registers)."""
    return SUBLANE, next(c for c in (512, 256, LANE) if W % c == 0)


def _datapath(grid: GridSpec, radius: int, rows: int, W: int,
              idx: Tuple, refs, store) -> None:
    """Channel production + PE pipeline + output store over a ``[rows,
    W]`` region whose tap bank is already formed, block by block.

    Each memory-VC channel *selects* its producer from the bank by its
    SMEM tap select (ingest plans are runtime settings, like VC muxes); a
    select of the trailing zero row takes the channel's const value
    instead.  ``store(last_ref, r0, c0)`` consumes the last level of the
    block at row ``r0``, lane ``c0``.
    """
    tap_ref, op_ref, sel_ref, const_ref, bank_ref, chan_ref, lvl_ref = refs
    rb, cb = _blocks(W)
    n_cb = W // cb
    zero_row = (2 * radius + 1) ** 2
    dtype = chan_ref.dtype

    def block(b, carry):
        r0 = pl.multiple_of((b // n_cb) * rb, rb)
        c0 = pl.multiple_of((b % n_cb) * cb, cb)
        for c in range(grid.num_inputs):
            tap = tap_ref[idx + (c,)]
            row = bank_ref[_clamp(tap, zero_row), pl.ds(r0, rb), pl.ds(c0, cb)]
            const = jnp.full(row.shape, const_ref[idx + (c,)]).astype(dtype)
            use_const = jnp.full(row.shape, tap, jnp.int32) == zero_row
            chan_ref[c] = jnp.where(use_const, const, row)
        store(_run_levels(grid, idx, op_ref, sel_ref, chan_ref, lvl_ref), r0, c0)
        return carry

    jax.lax.fori_loop(0, (rows // rb) * n_cb, block, 0)


def _in_extent(y, row0, col0, h, w):
    """``y`` (a ``[rows, lanes]`` block whose first pixel sits at canvas
    row ``row0``, column ``col0``) with every pixel outside the slot's
    frame extent ``[0, h) x [0, w)`` set to zero."""
    grow = row0 + jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
    gcol = col0 + jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    valid = jnp.logical_and(jnp.logical_and(grow >= 0, grow < h), gcol < w)
    return jnp.where(valid, y, jnp.zeros_like(y))


def _live_step(hw_ref, tile_rows: int):
    """Whether grid step ``(i, t)`` computes anything: its output rows
    ``[t * tile_rows, (t + 1) * tile_rows)`` hold a pixel of slot ``i``'s
    frame.  A slot of extent ``(0, 0)`` (a padded app slot) has no live
    step, and the tiles below a frame's last row are dead.
    :func:`grid_steps` counts the same predicate on the host."""
    i = pl.program_id(0)
    t = pl.program_id(1)
    return jnp.logical_and(t * tile_rows < hw_ref[i, 0], hw_ref[i, 1] > 0)


def _fused_batched_body(grid: GridSpec, radius: int, tile_rows: int,
                        shapes, *refs):
    """Fused-ingest megakernel body: one row-haloed slab -> outputs, per
    (app, row-tile) grid step, with the slab streamed HBM->VMEM by an
    in-kernel double-buffered DMA.

    ``frames_ref`` is the whole zero-padded frame stack left in HBM
    (``memory_space=ANY`` -- the block pipeline never copies it); each
    grid step DMAs its own ``[tile_rows + 2r, W]`` halo window straight
    out of the un-duplicated frame into one of two VMEM slab buffers
    (``slabs_ref``) and *starts the next step's window into the other
    buffer before computing*, so tile t+1 streams in while tile t's PE
    pipeline executes (see :func:`_slab_dma_pipeline`).  Halo rows are
    re-read from HBM only at tile seams (``2r`` rows per interior seam)
    -- never duplicated into an HBM-resident slab tensor.

    The rest is the whole Pixie data path inside the kernel instance: the
    tap bank (:func:`_form_bank`), then the memory-VC channels and the
    conventional PE pipeline block by block (:func:`_datapath`) -- all
    without the slab ever leaving VMEM.  The untiled layout is simply
    T == 1: one window covering the whole padded frame, same body, no
    second buffer ever filled.

    Only live steps (:func:`_live_step`) run the tap bank and the
    datapath, and they store zeros outside the slot's extent ``hw``; a
    dead step stores a zero block.  Every step still takes its part in
    the slab stream, so the double buffer rotates as it always does.
    """
    tap_ref, op_ref, sel_ref, outsel_ref, hw_ref, const_ref = (
        _Smem(r, s) for r, s in zip(refs, shapes))
    (frames_ref, o_ref, slabs_ref, dma_sems_ref,
     bank_ref, chan_ref, lvl_ref) = refs[len(shapes):]
    i = pl.program_id(0)
    t = pl.program_id(1)
    slot = _slab_dma_pipeline(frames_ref, slabs_ref, dma_sems_ref, tile_rows)
    live = _live_step(hw_ref, tile_rows)
    h, w = hw_ref[i, 0], hw_ref[i, 1]

    @pl.when(live)
    def _():
        _form_bank(radius, tile_rows, slabs_ref.at[slot], bank_ref)

        def store(last, r0, c0):
            for k in range(grid.num_outputs):
                y = _output(grid, (i,), outsel_ref, last, k)
                o_ref[0, k, pl.ds(r0, y.shape[-2]), pl.ds(c0, y.shape[-1])] = (
                    _in_extent(y, t * tile_rows + r0, c0, h, w))

        _datapath(grid, radius, tile_rows, o_ref.shape[-1], (i,),
                  (tap_ref, op_ref, sel_ref, const_ref, bank_ref, chan_ref,
                   lvl_ref), store)

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


def _round_up(n: int, align: int) -> int:
    return -(-int(n) // align) * align


def _frame_layout(H: int, W: int, halo: int, tile_rows, grid: GridSpec):
    """Tile height, tile count and the padded output canvas ``(Hp, Wp)``
    of one fused megakernel call.

    The kernels work in whole sublane tiles: tile heights (rounded by
    ``resolve_tile_rows(align=SUBLANE)``; a single tile is the frame
    rounded up), DMA slab heights and every in-kernel row count are
    multiples of ``SUBLANE``, and W is padded to a LANE multiple -- the
    output block ``(1, K, tile_rows, Wp)``, the HBM window slices, the
    VMEM ref slices and the lane rotates need all of it.  The zero pad
    rows and columns read exactly like the frame border and are sliced
    back off.  Interpret mode runs this same layout, so the CPU parity
    suites pin the code path the chip compiles.
    """
    tr = resolve_tile_rows(tile_rows, H, W, halo, grid, align=SUBLANE)
    n_tiles = num_row_tiles(H, tr)
    if n_tiles == 1:
        tr = _round_up(tr, SUBLANE)
    return tr, n_tiles, n_tiles * tr, _round_up(W, LANE)


def grid_steps(grid: GridSpec, halo: int, tile_rows, hw, H: int,
               W: int) -> Tuple[int, int]:
    """``(steps, live)`` of one fused or pipeline megakernel call over
    ``[N, H, W]`` canvases whose slots hold frames of extents ``hw``
    (``[N, 2]``; ``halo`` is the radius, a chain's total radius): the
    call's ``N * T`` grid steps, and how many of them are live by the
    kernel's own predicate (:func:`_live_step`), so the host can count
    what the device skips."""
    tr, n_tiles, _, _ = _frame_layout(H, W, halo, tile_rows, grid)
    hw = np.asarray(hw, np.int64).reshape(-1, 2)
    tiles = np.minimum(-(-np.maximum(hw[:, 0], 0) // tr), n_tiles)
    return len(hw) * n_tiles, int(np.where(hw[:, 1] > 0, tiles, 0).sum())


def _pad_frames(images, top: int, n_tiles: int, tile_rows: int,
                slab_rows: int, Wp: int):
    """Host side of a fused pallas_call: ONLY the zero pad (``top`` border
    rows; ragged-tile remainder + the last slab's reach at the bottom;
    lane remainder at the right) -- the halo windows themselves are
    sliced by the in-kernel DMA, never materialized in HBM."""
    _, H, W = images.shape
    bottom = (n_tiles - 1) * tile_rows + slab_rows - H - top
    return jnp.pad(images, ((0, 0), (top, bottom), (0, Wp - W)))


def _fused_scratch(grid: GridSpec, radius: int, slab_rows: int, rows: int,
                   W: int, dtype):
    """VMEM scratch of a fused body: the DMA double buffer + semaphores,
    the tap bank of ``rows`` rows, and the block-sized channels and level
    ping-pong buffer."""
    rb, cb = _blocks(W)
    return [
        pltpu.VMEM((2, slab_rows, W), dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.VMEM(((2 * radius + 1) ** 2, rows, W), dtype),
        pltpu.VMEM((grid.num_inputs, rb, cb), dtype),
        pltpu.VMEM((2, max(grid.pes_per_level), rb, cb), dtype),
    ]


def vcgra_fused_batched(
    grid: GridSpec,
    radius: int,
    settings: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],
    ingests: Tuple[jnp.ndarray, jnp.ndarray],
    images: jnp.ndarray,
    interpret: Optional[bool] = None,
    tile_rows=None,
    hw: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Batched fused-ingest megakernel: N raw frames, N tenants, ONE
    pallas_call -- the Pallas twin of
    ``interpreter.batched_fused_overlay_step`` (and of its row-tiled twin
    when ``tile_rows`` is set).

    ``settings``: dense banks (ops [N, L, max_w], sel [N, L, max_w, 2],
    out_sel [N, K]); ``ingests``: (tap_sel int32 [N, C], const_vals [N, C]
    in grid dtype), all scalar-prefetched into SMEM; ``images``: [N, H, W],
    cast to the grid dtype at entry exactly like the XLA path's
    ``form_tap_bank`` (so parity holds even for frames arriving in another
    dtype).  ``hw``: int32 [N, 2] true (rows, cols) of each app's frame
    inside the canvas (None: the whole canvas), scalar-prefetched too.
    Returns [N, num_outputs, H*W] in the grid dtype, zero outside each
    app's extent: a row tile below the frame, or every tile of a slot of
    extent ``(0, 0)``, runs no datapath at all (see
    :func:`_fused_batched_body`).

    Blocking: the pallas grid iterates (app, row-tile) over the ONE
    zero-padded frame stack, which stays in HBM (``memory_space=ANY``) --
    no host-side halo slab tensor is ever materialized.  ``tile_rows``
    (int, ``tiling.TILE_AUTO`` or None = whole frame) sets the tile
    height, in whole sublane tiles (see :func:`_frame_layout`); each grid step's ``[tile_rows +
    2*radius, Wp]`` halo window is streamed HBM->VMEM by the kernel's own
    double-buffered ``make_async_copy`` pipeline, so tile t+1's window is
    in flight while tile t's PE pipeline executes, each frame row crosses
    HBM->VMEM once, and halo rows are re-read only at tile seams.  VMEM
    holds the two slabs and the ``taps * tile_rows * Wp`` tap bank that
    ``tiling.slab_rows_per_budget`` budgets for, plus block-sized channel
    and level buffers.  Padding rows and columns are zeros read only as
    border and sliced back off -- bitwise-exact.

    (Why not ``pltpu.emit_pipeline``: its BlockSpec grids express
    *disjoint* blocks -- index maps are multiplied by the block shape --
    while halo windows overlap by ``2*radius`` rows; the manual
    two-slab/two-semaphore rotation is the same schedule emit_pipeline
    would build, with the overlapping source windows it cannot express.)
    """
    interpret = _resolve_interpret(interpret)
    tap_sel, const_vals = ingests
    images = jnp.asarray(images, grid.dtype)
    n_apps, H, W = images.shape
    if hw is None:
        hw = np.full((n_apps, 2), (H, W), np.int32)
    r = radius
    tr, n_tiles, Hp, Wp = _frame_layout(H, W, r, tile_rows, grid)
    slab_rows = _round_up(tr + 2 * r, SUBLANE)
    frames = _pad_frames(images, r, n_tiles, tr, slab_rows, Wp)
    K = grid.num_outputs
    smem, shapes = _smem_operands(jnp.asarray(tap_sel, jnp.int32), *settings,
                                  jnp.asarray(hw, jnp.int32), const_vals)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(smem),
        grid=(n_apps, n_tiles),
        # The padded frame stack stays in HBM; the kernel's DMA pipeline
        # owns the HBM->VMEM movement.
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, K, tr, Wp), lambda i, t, *_: (i, 0, t, 0)),
        scratch_shapes=_fused_scratch(grid, r, slab_rows, tr, Wp,
                                      images.dtype),
    )
    y = pl.pallas_call(
        functools.partial(_fused_batched_body, grid, radius, tr, shapes),
        out_shape=jax.ShapeDtypeStruct((n_apps, K, Hp, Wp), images.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="vcgra_fused_batched",
    )(*smem, frames)
    return y[:, :, :H, :W].reshape(n_apps, K, H * W)


# -- multi-stage pipeline megakernel -------------------------------------------
#
# The device-resident chain executor (``core/plan.py`` pipeline axis): a
# depth-S application chain runs as ONE pallas_call whose per-(app, tile)
# instance executes every stage back to back over the same VMEM slab --
# the DMA pipeline amortizes across the whole chain instead of paying one
# HBM round trip per stage.


def _pipeline_batched_body(grid: GridSpec, radii: Tuple[int, ...],
                           tile_rows: int, stage_rows: Tuple[int, ...],
                           shapes, *refs):
    """Multi-stage trapezoid body: one haloed slab -> final-stage outputs.

    The DMA schedule is exactly ``_fused_batched_body``'s double buffer,
    but the halo radius is the chain's TOTAL ``R = sum(radii)``: to emit
    ``tile_rows`` final rows, stage 0 must consume ``tile_rows + 2R`` input
    rows, and each stage shaves its own ``2 * r_i`` -- a trapezoid of
    working regions narrowing toward the output tile.  Stage *i* therefore
    computes ``stage_rows[i] >= tile_rows + 2 * reach_i`` rows where
    ``reach_i`` is the sum of the *downstream* radii (rows later stages
    still need as halo); rows past the trapezoid only ever feed rows past
    the next stage's trapezoid.

    Between stages the selected output channel (``outch_ref``, a runtime
    setting like every mux select) is re-masked against the app's true
    frame extent (``hw_ref``) into ``x_ref``, the next stage's input:
    slab rows outside ``[0, h)`` and columns outside ``[0, w)`` are
    canvas/halo padding whose *stage outputs* are generally nonzero (a
    threshold PE emits GT(0, c) there), but the next stage's line buffers
    must read zeros -- the same invariant the XLA chain keeps with
    ``interpreter.valid_pixel_mask``, which is what makes fused-vs-staged
    bitwise parity hold.  The global row of local row ``j`` in stage
    *i*'s output region is ``t * tile_rows - reach_i + j``.

    Settings banks carry a leading stage axis (``[S, N, ...]``; the
    ``(si, i)`` SMEM index prefix reuses the shared helpers), so one
    compiled kernel serves every depth-S chain on the grid -- the
    settings-register contract at chain scale.  The final stage stores
    zeros outside the extent too, and only live steps run the stages at
    all (:func:`_live_step`; a dead step stores a zero block), exactly as
    in the single-stage kernel.
    """
    (tap_ref, op_ref, sel_ref, outsel_ref, outch_ref, hw_ref,
     const_ref) = (_Smem(r, s) for r, s in zip(refs, shapes))
    (frames_ref, o_ref, slabs_ref, dma_sems_ref, x_ref,
     bank_ref, chan_ref, lvl_ref) = refs[len(shapes):]
    i = pl.program_id(0)
    t = pl.program_id(1)
    slot = _slab_dma_pipeline(frames_ref, slabs_ref, dma_sems_ref, tile_rows)
    live = _live_step(hw_ref, tile_rows)
    h, w = hw_ref[i, 0], hw_ref[i, 1]
    W = o_ref.shape[-1]
    last_stage = len(radii) - 1

    @pl.when(live)
    def _():
        src = slabs_ref.at[slot]         # haloed rows of the whole chain
        for si, r in enumerate(radii):   # chain static; settings runtime
            reach = sum(radii[si + 1:])
            rows = stage_rows[si]
            _form_bank(r, rows, src, bank_ref)

            if si == last_stage:
                def store(last, r0, c0, si=si):
                    for k in range(grid.num_outputs):
                        y = _output(grid, (si, i), outsel_ref, last, k)
                        o_ref[0, k, pl.ds(r0, y.shape[-2]),
                              pl.ds(c0, y.shape[-1])] = _in_extent(
                            y, t * tile_rows + r0, c0, h, w)
            else:
                def store(last, r0, c0, si=si, reach=reach):
                    # The forwarded channel is output k = out_ch of the
                    # stage, i.e. last-level row out_sel[k]
                    # (forward_stage_output).
                    k = _clamp(outch_ref[si, i], grid.num_outputs)
                    y = _output(grid, (si, i), outsel_ref, last, k)
                    x_ref[pl.ds(r0, y.shape[0]), pl.ds(c0, y.shape[1])] = (
                        _in_extent(y, t * tile_rows - reach + r0, c0, h, w))

            _datapath(grid, r, rows, W, (si, i),
                      (tap_ref, op_ref, sel_ref, const_ref, bank_ref,
                       chan_ref, lvl_ref), store)
            src = x_ref

    @pl.when(jnp.logical_not(live))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)


def vcgra_pipeline_batched(
    grid: GridSpec,
    radii: Tuple[int, ...],
    settings: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray],
    ingests: Tuple[jnp.ndarray, jnp.ndarray],
    out_chs: jnp.ndarray,
    hw: jnp.ndarray,
    images: jnp.ndarray,
    interpret: Optional[bool] = None,
    tile_rows=None,
) -> jnp.ndarray:
    """Depth-S pipeline megakernel: N chained tenants, ONE pallas_call --
    the Pallas twin of the plan layer's fused pipeline executors.

    ``settings``: stage-stacked dense banks (ops [S, N, L, max_w], sel
    [S, N, L, max_w, 2], out_sel [S, N, K]); ``ingests``: per-stage tap
    plans (tap_sel int32 [S, N, C], const_vals [S, N, C] in grid dtype;
    stage *i*'s selects index a radius-``radii[i]`` bank); ``out_chs``:
    int32 [S, N], the channel stage *i* feeds forward (the last stage's
    row is carried for shape uniformity but never read); ``hw``: int32
    [N, 2] true (rows, cols) of each app's frame inside the canvas;
    ``images``: [N, H, W].  Returns [N, num_outputs, H*W] in grid dtype,
    zero outside each app's extent (a padded slot carries ``(0, 0)`` and
    runs no datapath).

    The frame stack is zero-padded by the chain's TOTAL radius
    ``R = sum(radii)`` rows (plus the layout's sublane alignment, see
    :func:`_frame_layout`) and stays in HBM; each (app, row-tile) step
    DMAs one haloed window into the 2-slot VMEM double buffer and runs
    the whole stage trapezoid on it (see ``_pipeline_batched_body``), so
    every frame row crosses HBM->VMEM once *per chain*, not once per
    stage.
    """
    interpret = _resolve_interpret(interpret)
    radii = tuple(int(r) for r in radii)
    tap_sel, const_vals = ingests
    images = jnp.asarray(images, grid.dtype)
    n_apps, H, W = images.shape
    R = sum(radii)
    tr, n_tiles, Hp, Wp = _frame_layout(H, W, R, tile_rows, grid)
    # Rows each stage computes: its trapezoid height tile_rows + 2*reach,
    # rounded to whole sublane tiles.  Stage 0 reads
    # the slab; later stages read x_ref, which holds the previous stage's
    # rows; the bank is sized by stage 0, the tallest.
    rows = tuple(_round_up(tr + 2 * sum(radii[si + 1:]), SUBLANE)
                 for si in range(len(radii)))
    slab_rows = _round_up(max(tr + 2 * R, rows[0] + 2 * radii[0]), SUBLANE)
    x_rows = max([rows[si] + 2 * radii[si] for si in range(1, len(radii))]
                 + list(rows[:-1]))
    frames = _pad_frames(images, R, n_tiles, tr, slab_rows, Wp)
    K = grid.num_outputs
    scratch = _fused_scratch(grid, max(radii), slab_rows, rows[0], Wp,
                             images.dtype)
    scratch.insert(2, pltpu.VMEM((x_rows, Wp), images.dtype))
    smem, shapes = _smem_operands(
        jnp.asarray(tap_sel, jnp.int32), *settings,
        jnp.asarray(out_chs, jnp.int32), jnp.asarray(hw, jnp.int32),
        const_vals,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(smem),
        grid=(n_apps, n_tiles),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, K, tr, Wp), lambda i, t, *_: (i, 0, t, 0)),
        scratch_shapes=scratch,
    )
    y = pl.pallas_call(
        functools.partial(_pipeline_batched_body, grid, radii, tr, rows,
                          shapes),
        out_shape=jax.ShapeDtypeStruct((n_apps, K, Hp, Wp), images.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="vcgra_pipeline_batched",
    )(*smem, frames)
    return y[:, :, :H, :W].reshape(n_apps, K, H * W)
