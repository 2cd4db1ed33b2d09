"""Logical-axis sharding constraints that degrade to no-ops off-mesh.

``constrain(x, "batch", None, "model")`` applies a
``with_sharding_constraint`` against the ambient mesh (the ``with mesh:``
context used by the dry-run and the real launcher); under no mesh (CPU
unit tests) it is the identity, so model code can sprinkle constraints
freely.

The overlay dispatch pipeline (``core/plan.py``) uses the mesh helpers
below.  :class:`MeshSpec` is the structured device-placement axis of an
``OverlayPlan``: ``app`` shards the leading app (N) axis -- embarrassingly
parallel, PR 4 -- and ``rows`` shards the pixel-row axis of fused frames
into contiguous bands whose radius-wide seam halos are exchanged with a
``ppermute`` collective (:func:`halo_exchange_rows`), so one huge frame
can span devices.  ``build_mesh`` realizes a spec against the local
devices (None when the host cannot honor it -- the single-device bitwise
fallback); ``shard_apps`` / ``shard_apps_rows`` wrap a batched overlay
executor in ``shard_map`` over the 1-D / 2-D mesh."""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def _ambient_mesh():
    try:
        from jax._src import mesh as mesh_lib

        m = mesh_lib.thread_resources.env.physical_mesh
        if m is not None and not m.empty:
            return m
    except Exception:
        pass
    return None


def _resolve(logical: Optional[str], mesh) -> Optional[object]:
    if logical is None:
        return None
    if logical == "batch":
        axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        if not axes:
            return None
        return axes if len(axes) > 1 else axes[0]
    return logical if logical in mesh.axis_names else None


def constrain(x, *logical_axes: Optional[str]):
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    if len(logical_axes) != x.ndim:
        raise ValueError(f"spec {logical_axes} vs rank {x.ndim}")
    spec = P(*(_resolve(a, mesh) for a in logical_axes))
    return jax.lax.with_sharding_constraint(x, spec)


# -- mesh sharding for the overlay dispatch pipeline ---------------------------

APP_AXIS = "app"
ROW_AXIS = "rows"


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """The device-placement axis of an ``OverlayPlan``, as structured data.

    ``app``  how many ways the leading app (N) axis of a batched dispatch
             is sharded (the PR 4 axis, formerly a bare int kwarg);
    ``rows`` how many contiguous pixel-row bands a fused frame is split
             into across devices -- each shard owns ``band = H / rows``
             output rows and receives its seam neighbours' ``radius`` edge
             rows via :func:`halo_exchange_rows` before running the
             *unchanged* per-shard executor (the PR 7 in-kernel DMA
             pipeline composes per shard; the slab it sees is just a
             shorter frame).

    Frozen and hashable: the spec lives inside the plan, so it IS part of
    THE cache key.  ``MeshSpec()`` is the single-device identity;
    ``MeshSpec(app=k)`` is exactly the placement the deprecated
    bare-int device kwarg used to mean, and produces the same plan key,
    so pre-2-D executable populations are reused unchanged.
    """

    app: int = 1
    rows: int = 1

    def __post_init__(self):
        for name in ("app", "rows"):
            v = getattr(self, name)
            if not isinstance(v, int) or isinstance(v, bool) or v < 1:
                raise ValueError(
                    f"MeshSpec.{name} must be an int >= 1, got {v!r}"
                )

    @property
    def size(self) -> int:
        """Total devices the spec asks for (``app * rows``)."""
        return self.app * self.rows

    def app_only(self) -> "MeshSpec":
        """The 1-D projection of this spec: same app-axis width, no row
        sharding.  Unfused dispatches use it (pre-packed channels carry no
        row structure to band-shard)."""
        return MeshSpec(app=self.app)

    def shape(self) -> Tuple[int, int]:
        """``(app, rows)`` -- the stats/bench stamp of the spec."""
        return (self.app, self.rows)

    def __str__(self) -> str:
        return f"{self.app}x{self.rows}"


def build_mesh(spec: MeshSpec) -> Optional[Mesh]:
    """Realize a :class:`MeshSpec` against the local devices.

    ``MeshSpec(app=k)`` yields the same 1-D ``("app",)`` mesh as the
    historical app-axis path; ``rows > 1`` yields a 2-D
    ``("app", "rows")`` mesh where consecutive devices form one app
    shard's row band (row neighbours adjacent, so seam ``ppermute``
    traffic stays between nearby devices).  Returns ``None`` when the
    spec is the single-device identity or the host has fewer local
    devices than ``spec.size`` -- callers fall back to the single-device
    path, which is bitwise identical; the fleet records the degradation
    in ``FleetStats`` so dashboards see the parallelism actually granted.
    """
    if spec.size <= 1:
        return None
    avail = jax.local_devices()
    if len(avail) < spec.size:
        return None
    devs = np.asarray(avail[: spec.size])
    if spec.rows == 1:
        return Mesh(devs, (APP_AXIS,))
    return Mesh(devs.reshape(spec.app, spec.rows), (APP_AXIS, ROW_AXIS))


#: ``jax.shard_map`` with the replication check off (the overlay bodies
#: mix per-app and replicated values freely).
_shard_map = functools.partial(jax.shard_map, check_vma=False)


def app_mesh(devices: int, axis: str = APP_AXIS) -> Optional[Mesh]:
    """A 1-D mesh over the first ``devices`` local devices, for sharding
    the app (N) axis of batched overlay dispatch.

    Returns ``None`` when ``devices <= 1`` or the host has fewer local
    devices than requested -- callers fall back to the single-device
    path, which is bitwise identical (the app axis is embarrassingly
    parallel), so a plan asking for more parallelism than the host offers
    degrades instead of erroring, mirroring :func:`constrain`.
    """
    if devices <= 1:
        return None
    avail = jax.local_devices()
    if len(avail) < devices:
        return None
    return Mesh(np.asarray(avail[:devices]), (axis,))


def shard_apps(fn: Callable, mesh: Mesh, num_args: int,
               axis: str = APP_AXIS) -> Callable:
    """shard_map ``fn`` over the leading app axis of all ``num_args``
    operands (pytrees whose every leaf carries a leading N) and of the
    output.  The per-app computation of the batched overlay executors is
    independent along N (the flat-gather offsets are local to each app),
    so sharded outputs are bitwise identical to the single-device run.
    Callers must pad N to a multiple of the mesh size first
    (``plan._with_app_padding``)."""
    spec = P(axis)
    return _shard_map(
        fn, mesh=mesh, in_specs=(spec,) * num_args, out_specs=spec
    )


def halo_exchange_rows(slab: jnp.ndarray, radius: int, rows: int,
                       axis: str = ROW_AXIS) -> jnp.ndarray:
    """Exchange the radius-wide seam halos of a row-band shard.

    Inside a ``shard_map`` over ``rows`` row shards, each shard holds a
    contiguous band ``[n, band, W]`` of frame rows.  A stencil of tap
    ``radius`` r needs r rows above and below the band: mid-frame those
    are the *neighbour shard's* edge rows, at the frame border they are
    zeros (``form_tap_bank``'s zero-pad semantics).  ``jax.lax.ppermute``
    gives both for free -- each shard sends its bottom r rows down and its
    top r rows up, and a shard named as nobody's destination receives
    zeros -- so the concatenated ``[n, band + 2r, W]`` slab reads exactly
    like a ``band + 2r``-row frame whose borders happen to be real
    neighbour pixels.  Radius 0 is the identity: no collective is emitted
    (jaxpr-checkable), so radius-0 row sharding costs no communication.
    """
    r = int(radius)
    if r <= 0:
        return slab
    down = [(i, i + 1) for i in range(rows - 1)]   # my bottom rows -> next
    up = [(i + 1, i) for i in range(rows - 1)]     # my top rows -> previous
    above = jax.lax.ppermute(slab[:, -r:, :], axis, down)
    below = jax.lax.ppermute(slab[:, :r, :], axis, up)
    return jnp.concatenate([above, slab, below], axis=1)


def shard_apps_rows(fn: Callable, mesh: Mesh, radius: int,
                    app_axis: str = APP_AXIS,
                    row_axis: str = ROW_AXIS) -> Callable:
    """shard_map a batched *fused* overlay executor over a 2-D
    ``(app, rows)`` mesh: apps shard the leading N axis (as
    :func:`shard_apps`), rows shard the frame's pixel-row axis into
    contiguous bands.

    Each shard runs the UNCHANGED inner executor on its haloed band --
    after :func:`halo_exchange_rows` the ``[n, band + 2r, W]`` slab is
    indistinguishable from a short frame, so row tiling and the in-kernel
    DMA pipeline lower per shard exactly as they would per frame -- and
    keeps the middle ``band`` output rows: the discarded first/last r
    rows are the ones whose taps read the slab's *synthetic* zero border
    instead of rows two shards away, and every kept row's taps land on
    real band/halo rows, which is why sharded output is bitwise equal to
    the single-device run.  Callers pad H to ``band * rows`` with
    ``band >= radius`` first (``plan._with_mesh_padding``) so one
    single-hop exchange always suffices.

    The flat pixel axis of the output ``[N, K, H * W]`` is row-major, so
    each shard's ``band * W`` pixels are one contiguous block and the
    out-spec ``P(app, None, rows)`` reassembles frames with no data
    movement.

    The executor sees band-local rows, so it runs on the whole haloed
    band (extent None); the apps' extents ``hw`` (int32 ``[N, 2]`` or
    None) then zero the kept rows outside each frame by their global row
    index, as every executor's output contract asks.
    """
    rows = mesh.shape[row_axis]
    r = int(radius)

    def banded(configs, ingests, hw, slab):
        haloed = halo_exchange_rows(slab, r, rows, axis=row_axis)
        ys = fn(configs, ingests, None, haloed)
        n, band, W = slab.shape
        ys = ys.reshape(n, -1, band + 2 * r, W)[:, :, r:r + band, :]
        if hw is not None:
            ys = jnp.where(_band_extent(hw, band, W, row_axis)[:, None], ys, 0)
        return ys.reshape(n, ys.shape[1], band * W)

    return _shard_map(
        banded, mesh=mesh,
        in_specs=(P(app_axis), P(app_axis), P(app_axis),
                  P(app_axis, row_axis)),
        out_specs=P(app_axis, None, row_axis),
    )


def _band_extent(hw: jnp.ndarray, band: int, W: int,
                 row_axis: str) -> jnp.ndarray:
    """``[n, band, W]`` bool mask of each app's frame extent ``hw`` over
    this shard's row band; a band row's global index is recovered from
    ``axis_index(rows) * band``."""
    row0 = jax.lax.axis_index(row_axis) * band
    rows_in = ((row0 + jnp.arange(band, dtype=jnp.int32))[None, :, None]
               < hw[:, 0][:, None, None])
    cols_in = (jnp.arange(W, dtype=jnp.int32)[None, None, :]
               < hw[:, 1][:, None, None])
    return jnp.logical_and(rows_in, cols_in)


def shard_pipeline_rows(stage_fn, mesh: Mesh, radii,
                        app_axis: str = APP_AXIS,
                        row_axis: str = ROW_AXIS) -> Callable:
    """Row-band sharding for PIPELINE plans: the 2-D mesh twin of
    :func:`shard_apps_rows` with a per-stage seam halo exchange *between*
    stages, so a whole chain's intermediates never leave their shard.

    Each stage re-runs :func:`halo_exchange_rows` at its own radius on the
    current band (the chain's intermediate), executes the unchanged
    batched fused stage on the haloed slab, crops the synthetic-border
    rows back off, then zeroes everything outside each app's true frame
    region (``hw``) before feeding the next stage -- without the mask,
    stage outputs on canvas/band padding (nonzero: their taps read real
    rows) would poison the next stage's border, which the staged oracle
    reads as zeros.  The mask needs each band row's GLOBAL row index,
    recovered from ``axis_index(rows) * band``.  Callers pad H to
    ``band * rows`` with ``band >= max(radii)`` first
    (``plan._with_pipeline_mesh_padding``) so every exchange is
    single-hop.

    Operands: ``(stage_settings, hw, images)`` -- per-stage
    ``(configs, ingests, out_ch)`` triples plus the int32 ``[N, 2]``
    valid-region sizes, all leaves leading with N.
    """
    rows = mesh.shape[row_axis]
    depth = len(radii)

    def banded(stage_settings, hw, slab):
        n, band, W = slab.shape
        valid = _band_extent(hw, band, W, row_axis)
        x = slab
        ys = None
        for si, r in enumerate(radii):
            r = int(r)
            haloed = halo_exchange_rows(x, r, rows, axis=row_axis)
            ys = stage_fn(r, stage_settings[si][0], stage_settings[si][1],
                          None, haloed)
            ys = ys.reshape(n, -1, band + 2 * r, W)[:, :, r:r + band, :]
            if si < depth - 1:
                out_ch = stage_settings[si][2]
                y = jnp.take_along_axis(
                    ys, out_ch.astype(jnp.int32)[:, None, None, None], axis=1
                )[:, 0]
                x = jnp.where(valid, y, 0)
        ys = jnp.where(valid[:, None], ys, 0)
        return ys.reshape(n, ys.shape[1], band * W)

    return _shard_map(
        banded, mesh=mesh,
        in_specs=(P(app_axis), P(app_axis), P(app_axis, row_axis)),
        out_specs=P(app_axis, None, row_axis),
    )


def constrain_time_mixer(x):
    """Batch-split a recurrent mixer's input over EVERY divisible mesh axis.

    Recurrent scans (sLSTM steps, GLA chunks) cannot parallelise over
    'model', so the model axis would sit idle computing replicas; instead
    the batch dim absorbs it as extra data parallelism where divisibility
    allows (xlstm train: 16x per-device compute cut; §Perf)."""
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    axes = []
    prod = 1
    for a in ("pod", "data", "model"):
        if a in mesh.axis_names and x.shape[0] % (prod * mesh.shape[a]) == 0:
            axes.append(a)
            prod *= mesh.shape[a]
    if not axes:
        return x
    spec = P(tuple(axes) if len(axes) > 1 else axes[0],
             *([None] * (x.ndim - 1)))
    return jax.lax.with_sharding_constraint(x, spec)
