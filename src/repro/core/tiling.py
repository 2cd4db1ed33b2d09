"""Shared padding/bucketing primitives for overlay dispatch tiling.

Every layer that shapes a dispatch -- the plan compiler
(``core/plan.py``), the fleet scheduler (``runtime/fleet.py``) and the
interpreter's pack helpers -- rounds to the same tiles from the same
module, so the compile-once contract ("one executable per padded tile
shape") has a single source of truth.  All padding here is *exact* by
construction: padded channels are never referenced by mux selects,
padded pixel columns are sliced off, and padded app slots replay an
already-valid config whose outputs are discarded.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp

#: Default on-chip working-set budget for the pixel-tiled fused executors
#: (bytes): what :func:`slab_rows_per_budget` lets the kernel's own VMEM
#: scratch grow to.  The TPU compiler's scoped-VMEM limit for one kernel
#: (16 MiB on a v5e) must also hold the pipelined output blocks and the
#: compiler's temporaries for the PE datapath, which this model does not
#: count; 8 MiB leaves that room at 1080p and 4K canvas widths (checked
#: by compiling the megakernels for a described v5e, see
#: tests/test_tpu_compile.py).
DEFAULT_VMEM_BUDGET_BYTES = 8 * 1024 * 1024

#: Lane width of the TPU vector unit: the compiled megakernels pad the
#: canvas width to a multiple of this (the lane axis of every block).
#: Re-exported by ``kernels/vcgra/vcgra_kernel.py``.
LANE = 128

#: Sublane count of a 32-bit TPU vreg tile: compiled tile heights are
#: whole multiples of this (or one tile covering the frame), the
#: second-minor block rule of the TPU compiler.
SUBLANE = 8

#: Sentinel ``OverlayPlan.tile_rows`` value: resolve the row-tile height
#: from the VMEM budget heuristic at trace time (shapes are static under
#: jit, so the pick is a trace-time constant and compile-once still holds
#: per frame shape).
TILE_AUTO = "auto"


def check_tile_rows(tile_rows: Union[int, str, None]) -> Union[int, str, None]:
    """Validate (and canonicalize) a ``tile_rows`` axis value -- ``None``
    (untiled), :data:`TILE_AUTO`, or an int >= 1.  Shared by the plan and
    the fleet so a misconfigured service fails at construction, not on
    its first fused flush."""
    if tile_rows is None or tile_rows == TILE_AUTO:
        return tile_rows
    try:
        tr = int(tile_rows)
    except (TypeError, ValueError):
        raise ValueError(
            f"tile_rows must be None, {TILE_AUTO!r} or an int >= 1, "
            f"got {tile_rows!r}"
        ) from None
    if tr < 1:
        raise ValueError(f"tile_rows must be >= 1 or {TILE_AUTO!r}, got {tr}")
    return tr


def slab_rows_per_budget(
    W: int,
    radius: int,
    *,
    num_inputs: int,
    max_level_width: int,
    itemsize: int,
    num_outputs: int = 1,
    budget_bytes: int = DEFAULT_VMEM_BUDGET_BYTES,
) -> int:
    """How many *output* rows of a fused row-tile fit the VMEM budget.

    The fused megakernel's VMEM per kernel instance is the tap bank
    (``(2r+1)^2`` rows of ``tile_rows * W`` elements), BOTH
    ``(tile_rows + 2*radius) * W`` slabs of the in-kernel DMA double
    buffer (tile t computes out of one while tile t+1 streams HBM->VMEM
    into the other) and the double-buffered ``num_outputs * tile_rows *
    W`` output block, plus a fixed set of block-sized buffers the PE
    datapath walks (``num_inputs`` channels and ``2 * max_level_width``
    level values, each one ``SUBLANE x 512`` block).  Solving
    ``bytes_per_output_row * tile_rows + fixed_bytes <= budget`` for
    ``tile_rows`` (the fixed part -- the slabs' ``2 * 2*radius * W`` halo
    rows and the datapath blocks -- comes off the budget up front, so the
    pick never exceeds it) gives the heuristic.
    """
    taps = (2 * radius + 1) ** 2
    width = max(W, 1)
    per_row = (taps + 2 + 2 * num_outputs) * width * itemsize
    fixed = (2 * (2 * radius) * width
             + (num_inputs + 2 * max_level_width) * SUBLANE * 512) * itemsize
    return max(1, (int(budget_bytes) - fixed) // per_row)


def aligned_tile_rows(tile_rows: int, align: int = SUBLANE) -> int:
    """Round a tile height DOWN to a multiple of ``align`` (at least
    ``align``) -- the compiled megakernels' whole-sublane-tile rule --
    while only ever shrinking the working set."""
    tr = int(tile_rows)
    return max(align, tr - tr % align)


def resolve_tile_rows(
    tile_rows: Union[int, str, None],
    H: int,
    W: int,
    radius: int,
    grid,
    budget_bytes: int = DEFAULT_VMEM_BUDGET_BYTES,
    align: Optional[int] = None,
) -> int:
    """Resolve a plan's ``tile_rows`` axis against one frame shape.

    ``None`` means untiled (one slab = the whole frame); :data:`TILE_AUTO`
    asks the VMEM budget heuristic (:func:`slab_rows_per_budget`); an int
    is taken verbatim.  The result is clamped to ``[1, H]`` --
    ``tile_rows >= H`` degenerates to the untiled single-slab layout, so
    small frames pay no tiling machinery under the auto default.

    ``align`` (the Pallas megakernels pass :data:`SUBLANE`; the XLA
    twin passes None -- no layout constraint there) rounds a pick that
    actually tiles, AUTO or explicit, to whole sublane tiles via
    :func:`aligned_tile_rows`: the kernels' block rule, so the heuristic,
    the XLA tiled twin and the DMA path all resolve through this ONE
    definition.  Tiling is exact, so the rounding changes the working
    set, never the output.
    """
    if tile_rows is None:
        return max(int(H), 1)
    if tile_rows == TILE_AUTO:
        picked = slab_rows_per_budget(
            W, radius,
            num_inputs=grid.num_inputs,
            max_level_width=max(grid.pes_per_level),
            itemsize=jnp.dtype(grid.dtype).itemsize,
            num_outputs=grid.num_outputs,
            budget_bytes=budget_bytes,
        )
    else:
        picked = int(tile_rows)
    picked = max(1, min(picked, int(H)))
    if align and picked < int(H):
        picked = min(aligned_tile_rows(picked, align), int(H))
    return picked


def num_row_tiles(H: int, tile_rows: int) -> int:
    """Row-tile count for one frame: ``ceil(H / tile_rows)``."""
    return -(-int(H) // int(tile_rows))


def halo_row_slabs(images: jnp.ndarray, tile_rows: int, radius: int) -> jnp.ndarray:
    """Overlapping row slabs for the tiled fused executors:
    ``[N, H, W] -> [N, T, tile_rows + 2*radius, W]``.

    The ONE definition of the halo math, shared by the XLA tiled twin and
    the Pallas megakernel so their slabs cannot drift apart (the bitwise
    parity contract between the two backends rides on it).  Rows are
    zero-padded by ``radius`` top and bottom plus the ragged-tile
    remainder; each slab is a ``lax.dynamic_slice`` window whose first and
    last ``radius`` rows are the halo -- real neighbour rows mid-frame,
    zeros at the frame border, exactly ``form_tap_bank``'s border.  The
    untiled case (T == 1) is the padded frame itself: no overlapping-slab
    materialization on the small-frame path.
    """
    n, H, W = images.shape
    r = int(radius)
    tr = int(tile_rows)
    T = num_row_tiles(H, tr)
    padded = jnp.pad(images, ((0, 0), (r, T * tr - H + r), (0, 0)))
    if T == 1:
        return padded[:, None]
    return jnp.stack(
        [
            jax.lax.dynamic_slice_in_dim(padded, t * tr, tr + 2 * r, axis=1)
            for t in range(T)
        ],
        axis=1,
    )


def hbm_read_model(
    H: int, W: int, radius: int, tile_rows: Union[int, None], itemsize: int,
    *, presliced: bool,
) -> Dict[str, float]:
    """Modelled per-frame HBM traffic of the two row-tiled fused
    lowerings, for the bench JSON's ``hbm_bytes_read`` column.

    ``presliced`` (the old Pallas lowering, still the XLA twin's layout):
    the host side of the call materializes overlapping halo slabs
    ``[T, tile_rows + 2r, W]`` in HBM -- the frame is read once to build
    them, the duplicated tensor is written, and the kernel then streams
    the whole duplicated tensor back in.  ``bytes_read`` is therefore
    ``frame + slabs = (2 + 2r*T/H) x`` the frame size, plus a
    ``(1 + 2r*T/H) x`` write that the un-duplicated path never pays.

    In-kernel DMA (``presliced=False``): the kernel DMAs overlapping
    windows straight out of the ONE zero-row-padded frame -- each frame
    row crosses HBM->VMEM once, halo rows are re-read only at the
    ``T - 1`` tile seams (``2r`` rows each), and nothing halo-shaped is
    ever written to HBM.  ``read_amplification`` is bytes_read over the
    raw frame size: ``~1x`` for real tile heights vs the pre-sliced
    path's ``>= 2x`` (the ``1 + 2r/tile_rows`` duplication, paid twice:
    once written, once read).
    """
    frame = int(H) * int(W) * int(itemsize)
    tr = max(int(H), 1) if tile_rows is None else min(int(tile_rows), int(H))
    T = num_row_tiles(H, tr)
    slab_bytes = T * (tr + 2 * int(radius)) * int(W) * int(itemsize)
    if presliced:
        read = frame + slab_bytes          # frame (to slice) + slab stream
        written = slab_bytes               # the duplicated halo tensor
    else:
        read = slab_bytes                  # seam halos only; no duplication
        written = 0
    return {
        "frame_bytes": frame,
        "tile_rows": tr,
        "n_tiles": T,
        "hbm_bytes_read": read,
        "hbm_halo_bytes_written": written,
        "read_amplification": read / frame if frame else 0.0,
    }


def row_band(H: int, rows: int, radius: int = 0) -> int:
    """Rows per shard band for 2-D ``(app, rows)`` mesh sharding:
    ``ceil(H / rows)``, floored at ``radius`` (and 1).

    The floor is what keeps the seam halo exchange single-hop: each row
    shard's stencil taps reach at most ``radius`` rows past its band, and
    :func:`repro.parallel.axes.halo_exchange_rows` fetches exactly the
    neighbour's ``radius`` edge rows -- legal only while every band holds
    at least ``radius`` rows, so a shard never needs pixels from two
    shards away.  Frames are padded to ``row_band(...) * rows`` total
    rows (``plan._with_mesh_padding``); the zero pad rows are read only
    as bottom-border zeros and their outputs sliced off, so the padding
    is exact in the same sense as :func:`halo_row_slabs`'s.
    """
    return max(-(-int(H) // int(rows)), int(radius), 1)


def round_up(n: int, tile: int) -> int:
    """Smallest multiple of ``tile`` that is >= ``n``."""
    return ((n + tile - 1) // tile) * tile


def pow2_bucket(n: int, floor: int) -> int:
    """Smallest power-of-two multiple of ``floor`` that is >= ``n``
    (``floor`` itself for small ``n``) -- the fleet's pixel/canvas bucket
    rule, bounding distinct compiled shapes to O(log max_size)."""
    b = max(floor, 1)
    while b < n:
        b *= 2
    return b


def pad_channels(x: jnp.ndarray, num_inputs: int) -> jnp.ndarray:
    """Zero-pad the channel axis of ``x: [k, batch]`` up to the grid's
    memory-VC width.  Applications rarely use every memory channel; mux
    selects never reference the padded rows, so batching apps with
    different input counts on one grid stays exact."""
    k = x.shape[0]
    if k > num_inputs:
        raise ValueError(f"app uses {k} input channels, grid has {num_inputs}")
    if k == num_inputs:
        return x
    return jnp.concatenate(
        [x, jnp.zeros((num_inputs - k,) + x.shape[1:], x.dtype)], axis=0
    )


def pad_batches(xs: Sequence[jnp.ndarray], pad_to: int) -> List[jnp.ndarray]:
    """Zero-pad every ``[channels, batch]`` input to ``pad_to`` columns."""
    return [
        jnp.pad(x, ((0, 0), (0, pad_to - x.shape[-1]))) if x.shape[-1] < pad_to else x
        for x in xs
    ]
