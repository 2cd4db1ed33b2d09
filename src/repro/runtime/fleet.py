"""Pixie fleet: a multi-tenant batched scheduler for VCGRA overlays.

The paper's economics (Sec. V-E) are compile-once / reconfigure-in-ms:
one physical overlay amortizes its ~1200 s FPGA compile across every
application mapped onto it.  This module pushes the amortization one step
further: because every application mapped on a grid yields
identically-shaped settings arrays, N *different* tenants can be stacked
(``VCGRAConfig.stack``) and executed by one vmapped overlay executable in
a single dispatch (a batched :class:`repro.core.plan.OverlayPlan`
compiled once by ``compile_plan``) -- the serving-throughput analogue of
resident multi-context bitstreams.  With a
:class:`~repro.parallel.axes.MeshSpec` the plan additionally shards every
dispatch over local devices: ``MeshSpec(app=k)`` splits the app axis k
ways, ``MeshSpec(app=k, rows=m)`` also row-bands fused frames over a 2-D
mesh with seam halo exchange (both bitwise-equal to the single-device
run).

Scheduling model:

* requests name an application (a :class:`DFG` or a library app name) plus
  its pixel inputs (named channels or a whole image);
* requests are grouped by :class:`GridSpec` -- only same-structure overlays
  share an executable;
* image requests take the **fused-ingest** path: the raw frame is kept at
  submit time and line-buffer formation (stencil tap slices) happens
  *inside* the batched dispatch (a fused batched ``OverlayPlan``)
  -- pack + dispatch + unpack are one executable, with per-app
  :class:`repro.core.ingest.IngestPlan` settings selecting each channel's
  producer; named-channel requests keep the host-packed path;
* each group is padded to fixed tiles -- the app axis to ``batch_tile``,
  the pixel axis (frame canvas for fused, flat batch for unfused) to
  power-of-two buckets -- so repeated flushes hit the same compiled
  executable (no shape-driven recompiles);
* fused dispatches are row-tiled on the pixel axis (``tile_rows``, default
  ``TILE_AUTO``: a VMEM budget heuristic that degenerates to untiled at
  smoke sizes) and frames ride a reused canvas pool; with
  ``ingest="async"`` the pipeline double-buffers -- pooled canvases are
  shipped via ``jax.device_put`` into a donated operand and outputs are
  unpacked lazily, so packing of flush k+1 overlaps the device execution
  of flush k;
* mapped configs are cached by DFG structural hash: a repeat tenant costs
  zero place/route work;
* compiled batched overlays are cached per grid in a small LRU.

All padding is exact: padded app slots replay an already-valid config on
zero inputs and are discarded; padded pixels (for fused requests: the
zero canvas right/below the frame, which taps read exactly like
``stencil_inputs``'s zero border) are sliced off -- so fleet outputs are
bitwise identical to sequential `Pixie` runs.
"""

from __future__ import annotations

import dataclasses
import math
import time
import warnings
from collections import OrderedDict
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import applications as app_lib
from repro.core import grid as gridlib
from repro.core import interpreter
from repro.core.bitstream import VCGRAConfig
from repro.core.dfg import DFG
from repro.core.grid import GridSpec
from repro.core.ingest import IngestPlan, check_ingest
from repro.core.pixie import map_app
from repro.core.plan import (
    OverlayExecutable, OverlayPlan, PipelineSpec, compile_plan, fallback_chain,
)
from repro.core.tiling import (
    TILE_AUTO, check_tile_rows, pow2_bucket, round_up, row_band,
)
from repro.parallel.axes import APP_AXIS, ROW_AXIS, MeshSpec, build_mesh
from repro.runtime.chaos import FaultInjector
from repro.runtime.fault_tolerance import HeartbeatMonitor
from repro.runtime.resilience import (
    BreakerBoard, PlanBuildError, PoisonedOutputError, QuarantinedError,
    RetryPolicy,
)


class LRUCache:
    """Tiny ordered-dict LRU with hit/miss counters (no external deps)."""

    def __init__(self, capacity: int):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = capacity
        self._d: "OrderedDict[Any, Any]" = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key: Any) -> Optional[Any]:
        if key in self._d:
            self._d.move_to_end(key)
            self.hits += 1
            return self._d[key]
        self.misses += 1
        return None

    def put(self, key: Any, value: Any) -> List[Any]:
        """Insert; returns the keys evicted to make room (callers that
        cache executables log them so eviction churn names the exact
        plan involved)."""
        self._d[key] = value
        self._d.move_to_end(key)
        evicted = []
        while len(self._d) > self.capacity:
            k, _ = self._d.popitem(last=False)
            evicted.append(k)
            self.evictions += 1
        return evicted

    def __len__(self) -> int:
        return len(self._d)

    def __contains__(self, key: Any) -> bool:
        return key in self._d


@dataclasses.dataclass
class FleetRequest:
    """One tenant's work item.

    ``app``: a DFG, a pre-mapped VCGRAConfig, or a library app name
    (``repro.core.applications.ALL_APPS``).
    ``inputs``: named memory-VC channels, or ``image``: an [H, W] array fed
    through the stencil line-buffer helper.  ``grid`` overrides the fleet's
    default overlay for this request.

    ``pipeline`` (instead of ``app``): an ordered chain of applications --
    stage i's selected output (``out_channels[i]``, default channel 0)
    feeds stage i+1's ingest taps.  The whole chain executes as ONE
    device-resident dispatch (a pipeline :class:`OverlayPlan`); a
    single-stage chain demotes to the plain fused path at submit, so it
    batches (and caches) exactly like an ``app=`` request.  Pipeline
    requests take ``image=`` frames only (every stage is fused ingest).
    """

    app: Union[DFG, VCGRAConfig, str, None] = None
    inputs: Optional[Dict[str, Any]] = None
    image: Optional[Any] = None
    grid: Optional[GridSpec] = None
    pipeline: Optional[Sequence[Union[DFG, VCGRAConfig, str]]] = None
    out_channels: Optional[Sequence[int]] = None


@dataclasses.dataclass
class FleetStats:
    backend: str = "xla"         # execution backend of every dispatch
    devices: int = 1             # app-axis mesh width of every dispatch
    ingest: str = "sync"         # ingest pipelining mode of every dispatch
    # Mesh truthfulness: the (app, rows) shape the fleet was ASKED for vs
    # the shape actually realized against the host's local devices.
    # build_mesh degrades to the single-device bitwise fallback instead of
    # erroring when the host is short, so without this stamp a serving
    # dashboard would happily report a "16-way" fleet running on one chip;
    # the bench JSON carries all three fields (see
    # benchmarks/fleet_throughput.py).
    mesh_requested: Tuple[int, int] = (1, 1)
    mesh_granted: Tuple[int, int] = (1, 1)
    mesh_degraded: bool = False
    # Devices the most recent dispatch's output actually spans (its
    # sharding's device set): the placement mesh_granted promises, as
    # observed on the result.
    output_devices: int = 0
    canvas_pool_hits: int = 0    # frame canvases reused instead of allocated
    # Per-device canvas reuse for sharded async fleets: the pool is keyed
    # by mesh device, so each shard's ingest fills (and ships) its own
    # host buffer instead of serializing through one whole-batch canvas.
    # Keyed by str(device.id) -> hit count; empty for unsharded fleets.
    canvas_pool_device_hits: Dict[str, int] = dataclasses.field(
        default_factory=dict
    )
    submitted: int = 0
    executed: int = 0
    dispatches: int = 0          # batched overlay launches
    fused_dispatches: int = 0    # of which took the fused-ingest path
    pipeline_dispatches: int = 0  # of which chained depth>1 pipeline specs
    # Streaming-scheduler preemptions: batches whose composition was
    # re-sorted mid-selection because an urgent-deadline request flipped
    # ahead of the staged (priority, arrival) order -- see
    # StreamingFrontend._select_batch.
    preempted_batches: int = 0
    # Dispatches launched with fewer real requests than the app tile --
    # the continuous-batching scheduler fires these when a deadline
    # approaches rather than waiting for a full tile, and the serving
    # bench asserts they actually happen under deadline pressure.
    partial_tile_dispatches: int = 0
    padded_app_slots: int = 0    # wasted N-axis slots from tile rounding
    # Grid steps (app slot x row tile) of the fused and pipeline pallas
    # megakernels, and the live ones: a step computes only where its
    # output rows hold a pixel of its slot's frame (padded slots carry
    # extent (0, 0)).  Counted on the host by the kernel's own predicate
    # (``kernels.vcgra.grid_steps``), per dispatch also on its
    # ``pixie.execute`` span (``steps=``, ``live=``).  XLA plans and mesh
    # plans (kernels per shard or per stage) add none.
    kernel_steps: int = 0
    kernel_steps_live: int = 0
    map_calls: int = 0           # place/route runs (config-cache misses)
    config_cache_hits: int = 0
    overlay_builds: int = 0      # batched executables built (per OverlayPlan)
    overlay_cache_hits: int = 0
    stack_bank_hits: int = 0     # stacked settings banks reused across flushes
    # Full plan-key stamp of every dispatch: "<plan.key()>|<padded tile>"
    # -> dispatch count.  Bench JSON and assertion/eviction messages name
    # the exact executable involved, not just the backend.
    dispatch_plans: Dict[str, int] = dataclasses.field(default_factory=dict)
    evicted_plans: List[str] = dataclasses.field(default_factory=list)
    # -- resilience telemetry (PR 10) ------------------------------------
    retries: int = 0             # re-dispatch attempts after a transient failure
    quarantined_requests: int = 0  # tickets isolated by bisection + failed
    # Dispatches served by a degraded plan from the fallback chain
    # (pallas->xla, 2-D mesh->app-only->single device, tiled->untiled)
    # because the primary plan failed or its breaker was open.
    fallback_dispatches: int = 0
    guard_failures: int = 0      # outputs rejected by the NaN/Inf guard
    straggler_flushes: int = 0   # flushes the HeartbeatMonitor flagged
    # Every circuit-breaker transition, in order: {"plan", "event", "t",
    # "consecutive_failures"}.  The list is SHARED with the fleet's
    # BreakerBoard, so it is always current without copying.
    breaker_events: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list
    )

    def stamp_dispatch(self, plan: OverlayPlan, tile: str) -> None:
        key = f"{plan.key()}|{tile}"
        self.dispatch_plans[key] = self.dispatch_plans.get(key, 0) + 1

    def as_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def _extents(items: Sequence[Tuple[int, "_Prepared"]],
             n_tile: int) -> np.ndarray:
    """int32 ``[n_tile, 2]``: each item's true frame ``(rows, cols)`` in
    its app slot, ``(0, 0)`` for the padded slots.  A numpy operand: the
    dispatch ships it with its own arguments, no eager device op."""
    hw = np.zeros((n_tile, 2), np.int32)
    for i, (_, p) in enumerate(items):
        hw[i] = p.hw
    return hw


@dataclasses.dataclass
class _PooledCanvas:
    """One reusable frame canvas plus the device_put still reading it.

    ``pending`` is the device array the async path last shipped from
    ``buf``: the host buffer may not be rewritten until that transfer
    completes, so :meth:`PixieFleet._canvas` blocks on it at *reuse* time
    (when it is long done) instead of on the ship's critical path -- the
    depth-2 rotation is what makes the deferred block almost always free.
    """

    buf: np.ndarray
    pending: Optional[Any] = None


@dataclasses.dataclass
class _Prepared:
    """A submit-time-validated work item awaiting flush."""

    grid: GridSpec
    cfg: VCGRAConfig
    kind: str          # "image" (fused ingest) | "channels" | "pipeline"
    payload: Any                 # np [H, W] raw frame | jnp [C, batch]
    hw: Optional[Tuple[int, int]]
    # Depth>1 chain spec for kind="pipeline" (depth-1 chains demote to
    # kind="image" at submit, so they share the single-stage plan cache).
    spec: Optional[PipelineSpec] = None


class PixieFleet:
    """Accepts per-app requests and serves them in vmapped batches.

    >>> fleet = PixieFleet()
    >>> t1 = fleet.submit(FleetRequest(app="sobel_x", image=img))
    >>> t2 = fleet.submit(FleetRequest(app="threshold", image=img))
    >>> outs = fleet.flush()          # ONE overlay dispatch for both
    >>> outs[t1].shape
    (32, 32)
    """

    def __init__(
        self,
        default_grid: Optional[GridSpec] = None,
        batch_tile: int = 8,
        min_pixel_batch: int = 256,
        max_overlays: int = 8,
        max_configs: int = 256,
        max_retained_results: int = 1024,
        backend: str = "xla",
        mesh: Optional[MeshSpec] = None,
        ingest: str = "sync",
        tile_rows: Union[int, str, None] = TILE_AUTO,
        devices: Optional[int] = None,
        faults: Optional[FaultInjector] = None,
        retry: Optional[RetryPolicy] = None,
        breakers: Optional[BreakerBoard] = None,
        heartbeat: Optional[HeartbeatMonitor] = None,
        output_guard: Optional[bool] = None,
    ):
        self.default_grid = default_grid or gridlib.sobel_grid()
        # Execution backend for every dispatch: "xla" (the hand-lowered
        # jnp interpreter, the bitwise oracle) or "pallas" (the batched
        # VCGRA megakernels, interpreted off-TPU / compiled on TPU).
        self.backend = interpreter.check_backend(backend)
        # Device placement of every dispatch, as a structured MeshSpec:
        # app=k shards the N axis of every batched dispatch over k local
        # devices, rows=m additionally row-bands fused frames over a 2-D
        # (app, rows) mesh with seam halo exchange.  Both are
        # bitwise-equal to single-device and degrade to it when the host
        # has fewer devices -- see core/plan.py; the degradation is
        # recorded in FleetStats below.  The bare device-count kwarg is
        # the deprecated spelling of MeshSpec(app=k).
        if devices is not None:
            d = int(devices)
            if d < 1:
                raise ValueError(f"devices must be >= 1, got {devices}")
            if mesh is not None:
                raise ValueError(
                    "pass mesh=MeshSpec(...) or the deprecated bare device "
                    "count, not both"
                )
            warnings.warn(
                "the bare device-count kwarg of PixieFleet is deprecated: "
                f"pass mesh=MeshSpec(app={d}) instead",
                DeprecationWarning,
                stacklevel=2,
            )
            mesh = MeshSpec(app=d)
        if mesh is not None and not isinstance(mesh, MeshSpec):
            raise ValueError(f"mesh must be a MeshSpec, got {mesh!r}")
        self.mesh = mesh or MeshSpec()
        # Ingest pipelining: "sync" packs, dispatches and materializes in
        # strict order; "async" double-buffers -- pooled canvases shipped
        # with device_put into a donated operand, outputs unpacked lazily
        # so the *next* flush's packing overlaps this flush's device
        # execution.  Bitwise-identical; async results are jax arrays
        # (forced on first host read) instead of eager numpy.
        self.ingest = check_ingest(ingest)
        # Pixel-axis row tiling of the fused dispatch: TILE_AUTO (default)
        # lets the VMEM budget heuristic pick per frame shape (single slab
        # == untiled at smoke sizes), an int fixes the tile height, None
        # disables tiling.  All values are bitwise-identical.
        self.tile_rows = check_tile_rows(tile_rows)
        # Reused zero canvases for fused frame embedding, keyed by padded
        # tile shape; depth 2 under async ingest (flush k+1 packs one
        # buffer while flush k's device_put of the other completes).
        # LRU-bounded like every other fleet cache: a service whose group
        # sizes / frame buckets drift would otherwise pin two full
        # canvases per distinct shape forever.
        self._canvas_pool = LRUCache(8)
        # Jitted group unpackers for the async fused path, keyed by the
        # item shapes: ONE lazy dispatch slices every tenant's [H, W]
        # window out of the canvas outputs (per-item eager slicing costs
        # ~25 tiny host-dispatched ops per flush -- the async tax that
        # used to eat the overlap win at smoke sizes).
        self._unpack_fns = LRUCache(64)
        self.batch_tile = int(batch_tile)
        # App-axis tiles must also divide evenly across the mesh so the
        # plan executable never has to re-pad internally (padded_app_slots
        # then accounts for ALL padding).
        self._app_tile = math.lcm(self.batch_tile, self.mesh.app)
        self.min_pixel_batch = int(min_pixel_batch)
        # Fused frame canvases bucket H and W separately; the floor keeps
        # the same ~min_pixel_batch pixels per tile as the unfused path.
        self.min_image_side = max(1, int(math.isqrt(self.min_pixel_batch)))
        # Keyed by OverlayPlan (the one cache key of the plan pipeline).
        self._overlays = LRUCache(max_overlays)
        self._configs = LRUCache(max_configs)
        # Stacked settings banks: a repeat flush of the same tenant set
        # skips re-stacking N configs (keyed by their cache identities).
        self._banks = LRUCache(4 * max_overlays)
        # Truthful mesh stamping: probe what the host can actually grant
        # once, here, so dashboards never mistake the requested shape for
        # the effective one (build_mesh silently falls back to
        # single-device when local devices run short).
        granted = self.mesh
        if self.mesh.size > 1 and build_mesh(self.mesh) is None:
            granted = MeshSpec()
        self.stats = FleetStats(
            self.backend, self.mesh.app, self.ingest,
            mesh_requested=self.mesh.shape(), mesh_granted=granted.shape(),
            mesh_degraded=granted != self.mesh,
        )
        self._pending: List[Tuple[int, Tuple]] = []
        # Bounded: unredeemed tickets are evicted oldest-first so a service
        # that only consumes flush()'s return value cannot leak memory.
        self._results: "OrderedDict[int, np.ndarray]" = OrderedDict()
        self.max_retained_results = int(max_retained_results)
        self._next_ticket = 0
        # -- resilience (PR 10) ----------------------------------------------
        # Transient failures retry with a deterministic backoff, a
        # persistently failing plan degrades down its fallback chain
        # behind a per-plan-key circuit breaker, and a request no plan can
        # serve is isolated by bisection and fails ONLY its own ticket
        # (stored in _failures, raised by result()).  One exception: on
        # an UNARMED fleet (no faults=, breakers= or heartbeat=) a primary
        # plan that fails to build or lower fails the batch with
        # PlanBuildError instead of degrading -- a kernel the device
        # compiler refuses must surface, not be served by the XLA oracle.
        # The policy objects are pure host control flow -- on the happy
        # path they cost a dict lookup per flush group.
        self.faults = faults
        self.retry = retry or RetryPolicy()
        self.breakers = breakers or BreakerBoard()
        # Flush wall times feed the seed HeartbeatMonitor; a flagged
        # straggler flush counts as a breaker failure for every plan it
        # dispatched -- but only on an armed fleet (the caller opted into
        # chaos/breaker tuning), so CI noise can never degrade a vanilla
        # fleet's plans.
        self.heartbeat = heartbeat if heartbeat is not None else HeartbeatMonitor()
        self._armed = (
            faults is not None or breakers is not None or heartbeat is not None
        )
        # NaN/Inf output guard (inexact dtypes only -- integer fabrics
        # cannot encode NaN).  Defaults on exactly when faults are
        # installed: the guard forces async outputs eagerly, which would
        # tax the happy path's ingest overlap.
        self._guard = bool(faults is not None if output_guard is None
                           else output_guard)
        # Per-ticket failures awaiting redemption: result() raises them,
        # front-ends drain them via pop_failures().  Bounded like _results.
        self._failures: "OrderedDict[int, BaseException]" = OrderedDict()
        # Per-flush scratch: breakers owed a success at flush end (the
        # success is deferred so a straggler flush can convert it into a
        # breaker failure), the (plan, operand shapes) an unarmed fleet
        # already found to compile cleanly after a failure (so the ladder
        # re-lowers each at most once per flush), and the memoized
        # fallback chains.
        self._flush_successes: List[Tuple[Any, str]] = []
        self._flush_compiled: set = set()
        self._chain_cache = LRUCache(64)
        self.stats.breaker_events = self.breakers.events
        # pack_s accumulates host-side input preparation (submit and the
        # per-dispatch embed, bank and ship); flush_started/flush_s stamp
        # the most recent flush (see :meth:`flush`).  Where the time inside
        # a flush goes is read from the ``pixie.*`` profiler spans.
        self.timings: Dict[str, float] = {"pack_s": 0.0}

    @property
    def devices(self) -> int:
        """App-axis mesh width (the reading side of the deprecated bare
        device-count surface; front-ends and stats consume it)."""
        return self.mesh.app

    # -- caches ---------------------------------------------------------------

    def config_for(self, app: Union[DFG, VCGRAConfig, str], grid: GridSpec) -> VCGRAConfig:
        """Mapped settings for (app, grid); place/route runs at most once
        per distinct DFG structure (the repeat-tenant fast path).

        Library-name requests additionally cache on (name, grid): a repeat
        tenant submitted by name skips even the DFG construction and
        structural hash (~0.1 ms/request -- the dominant per-request pack
        cost at smoke frame sizes, see BENCH pack_fraction_fused)."""
        if isinstance(app, str):
            key = (app, grid)
            cfg = self._configs.get(key)
            if cfg is not None:
                self.stats.config_cache_hits += 1
                return cfg
            cfg = self.config_for(app_lib.ALL_APPS[app](), grid)
            self._configs.put(key, cfg)
            return cfg
        if isinstance(app, VCGRAConfig):
            expected = (
                tuple((p,) for p in grid.pes_per_level),
                tuple((p, 2) for p in grid.pes_per_level),
                (grid.num_outputs,),
            )
            if app.config_shapes() != expected:
                raise ValueError(
                    f"config {app.app_name!r} was mapped on grid "
                    f"{app.grid_name!r}, which does not match {grid.name!r}"
                )
            return app
        dfg = app
        key = (dfg.structural_hash(), grid)
        cfg = self._configs.get(key)
        if cfg is not None:
            self.stats.config_cache_hits += 1
            return cfg
        cfg = map_app(dfg, grid)
        cfg.cache_key = f"{key[0]}@{grid.name}"
        self.stats.map_calls += 1
        self._configs.put(key, cfg)
        return cfg

    def plan_for_dispatch(self, grid: GridSpec, *, fused: bool,
                          radius: Optional[int] = None,
                          pipeline: Optional[Tuple[PipelineSpec, ...]] = None,
                          ) -> OverlayPlan:
        """The :class:`OverlayPlan` of one dispatch on this fleet: the
        fleet contributes its backend, mesh, tiling and ingest axes, the
        request group contributes grid/fusion/radius (or, for chained
        dispatches, the per-tenant pipeline specs -- radius then derives
        from the stages).  Unfused dispatches project the mesh to its app
        axis (pre-packed channels carry no row structure to band-shard)."""
        if pipeline is not None:
            return OverlayPlan(
                grid=grid, batched=True, pipeline=pipeline,
                backend=self.backend, mesh=self.mesh,
                tile_rows=self.tile_rows, ingest=self.ingest,
            )
        return OverlayPlan(
            grid=grid, batched=True, fused=fused, radius=radius,
            backend=self.backend,
            mesh=self.mesh if fused else self.mesh.app_only(),
            tile_rows=self.tile_rows if fused else None,
            ingest=self.ingest,
        )

    def overlay_executable(self, plan: OverlayPlan) -> OverlayExecutable:
        """The compiled executable for ``plan``, through the fleet's LRU:
        built once per distinct plan (THE cache key -- backend, fusion,
        radius, devices and grid all live in it), shared by every padded
        tile shape via XLA's own shape-keyed jit cache."""
        fn = self._overlays.get(plan)
        if fn is not None:
            self.stats.overlay_cache_hits += 1
            return fn
        if self.faults is not None:
            # Compile faults fire on cache MISSES only: a cached plan
            # cannot fail to compile.  A failing build is never cached,
            # so the spec keeps firing until exhausted -- exactly like a
            # real deterministic compile error.
            self.faults.fire("compile", (f"plan:{plan.key()}",))
        try:
            fn = compile_plan(plan)
        except Exception as exc:  # noqa: BLE001 -- unarmed: PlanBuildError on every ticket; armed: retry/fallback ladder
            if self._armed:
                raise
            raise PlanBuildError(plan.key(), exc) from exc
        self.stats.overlay_builds += 1
        for evicted in self._overlays.put(plan, fn):
            self.stats.evicted_plans.append(evicted.key())
        return fn

    def overlay_for(self, grid: GridSpec) -> OverlayExecutable:
        """The batched (pre-packed channels) executable for ``grid``."""
        return self.overlay_executable(self.plan_for_dispatch(grid, fused=False))

    def fused_overlay_for(self, grid: GridSpec, radius: int) -> OverlayExecutable:
        """The batched *fused-ingest* executable for ``grid``: raw frames
        in, line buffers formed inside the dispatch.  Ingest plans are
        runtime settings, so every app shares it."""
        return self.overlay_executable(
            self.plan_for_dispatch(grid, fused=True, radius=radius)
        )

    def overlay_executable_count(self, grid: Optional[GridSpec] = None) -> int:
        """Number of XLA executables compiled for a grid's batched overlays
        (fused and unfused combined; one per distinct padded tile shape, so
        1 when one path is in use and tiling is doing its job).  Returns -1
        when the running jax has no jit cache introspection (``_cache_size``
        is not public API); ``stats.overlay_builds`` is the version-stable
        counter."""
        grid = grid or self.default_grid
        counts = []
        for plan, fn in self._overlays._d.items():
            if plan.grid == grid:
                sizer = getattr(fn, "_cache_size", None)
                counts.append(int(sizer()) if callable(sizer) else -1)
        if not counts:
            return 0
        if any(c == -1 for c in counts):
            return -1
        return sum(counts)

    # -- request intake -------------------------------------------------------

    def submit(self, request: FleetRequest) -> int:
        """Queue one request; returns a ticket redeemed by :meth:`flush`.

        Mapping and input packing happen HERE, not at flush time: an
        unmappable app or a missing input raises immediately to its own
        submitter and can never poison a batch of other tenants' queued
        work.
        """
        if request.pipeline is not None:
            if request.app is not None:
                raise ValueError("give app= or pipeline=, not both")
            if request.image is None or request.inputs is not None:
                raise ValueError(
                    "pipeline requests take image= frames (every stage is "
                    "fused ingest), not inputs="
                )
        elif request.app is None:
            raise ValueError("exactly one of app= or pipeline= must be given")
        elif (request.inputs is None) == (request.image is None):
            raise ValueError("exactly one of inputs= or image= must be given")
        with TraceAnnotation("pixie.intake"):
            prepared = self._prepare(request)
        ticket = self._next_ticket
        self._next_ticket += 1
        self._pending.append((ticket, prepared))
        self.stats.submitted += 1
        return ticket

    def result(self, ticket: int) -> np.ndarray:
        """Redeem a flushed ticket (pops it from the retained results).
        A quarantined ticket raises its stored failure -- the typed
        QuarantinedError carrying the ticket and underlying cause."""
        if ticket in self._failures:
            raise self._failures.pop(ticket)
        try:
            return self._results.pop(ticket)
        except KeyError:
            raise KeyError(
                f"no retained result for ticket {ticket}: it was never "
                f"flushed, was already redeemed, or was evicted by the "
                f"retention bound (max_retained_results="
                f"{self.max_retained_results}); redeem tickets promptly or "
                f"raise the bound"
            ) from None

    def discard(self, ticket: int) -> None:
        """Drop a retained result without redeeming it (callers that consume
        flush()'s return value directly use this to release retention)."""
        self._results.pop(ticket, None)

    def _stacked_bank(self, grid: GridSpec, configs: List[VCGRAConfig],
                      fused: bool = False):
        """Stacked settings for a tenant set, cached across flushes when
        every config carries a cache identity (i.e. came through
        :meth:`config_for`).  For fused dispatches the bank also carries
        the stacked ingest-plan arrays (tap selects + const values)."""

        def build():
            stacked = VCGRAConfig.stack(configs)
            if not fused:
                return stacked
            plans = [c.ingest for c in configs]
            return stacked, IngestPlan.stack(plans, grid.dtype)

        keys = tuple(c.cache_key for c in configs)
        if any(k is None for k in keys):
            return build()
        bkey = (grid, keys, fused)
        stacked = self._banks.get(bkey)
        if stacked is not None:
            self.stats.stack_bank_hits += 1
            return stacked
        stacked = build()
        self._banks.put(bkey, stacked)
        return stacked

    def _canvas(self, shape: Tuple[int, ...], dtype,
                device=None) -> _PooledCanvas:
        """A frame canvas from the reuse pool (no per-flush numpy
        allocation in steady state), for the caller to zero and fill.
        Pool depth 2 under async ingest -- the double buffer: flush k+1
        packs one buffer while flush k's device_put of the other may
        still be copying; any pending ship is blocked on here, at reuse
        time, when it is long complete (sync mode materializes outputs
        before the next flush, so depth 1 and no pending ships).

        ``device`` keys the pool per mesh device for sharded async fleets
        (:meth:`_ship_sharded_frames`): each device's shard rotates its own
        depth-2 buffer pair, so one shard's still-copying ship never blocks
        another shard's fill.  Per-device reuse is counted separately in
        ``stats.canvas_pool_device_hits``."""
        key = (shape, np.dtype(dtype).str,
               None if device is None else device.id)
        pool = self._canvas_pool.get(key)
        if pool is None:
            pool = []
            self._canvas_pool.put(key, pool)
        depth = 2 if self.ingest == "async" else 1
        if len(pool) < depth:
            entry = _PooledCanvas(np.zeros(shape, dtype))
            pool.append(entry)
            return entry
        entry = pool.pop(0)
        pool.append(entry)
        self.stats.canvas_pool_hits += 1
        if device is not None:
            dkey = str(device.id)
            self.stats.canvas_pool_device_hits[dkey] = (
                self.stats.canvas_pool_device_hits.get(dkey, 0) + 1
            )
        if entry.pending is not None:
            with TraceAnnotation("pixie.canvas_wait"):
                try:
                    jax.block_until_ready(entry.pending)
                except RuntimeError:
                    # Donated and already consumed: execution only starts
                    # once its operands materialize, so the transfer out
                    # of this host buffer necessarily completed.
                    pass
            entry.pending = None
        return entry

    def _ship_frames(self, mesh, n_tile: int, Hb: int, Wb: int, dtype,
                     items) -> jnp.ndarray:
        """The frames of one fused or chained dispatch on the device:
        embedded top-left into one pooled zero canvas ``[n_tile, Hb, Wb]``
        on the host and shipped.  Sharded async plans ship per device
        (:meth:`_ship_sharded_frames`).

        Under async ingest the ship is ``jnp.array(copy=True)`` (a
        device_put of aligned numpy may alias the host buffer on the CPU
        backend, which would let the pooled buffer's next fill race
        still-unforced lazy outputs), and is not waited on: the pending
        record defers that wait to the buffer's reuse two flushes later."""
        if self.ingest == "async" and mesh is not None:
            return self._ship_sharded_frames(mesh, n_tile, Hb, Wb, dtype,
                                             items)
        entry = self._canvas((n_tile, Hb, Wb), dtype)
        with TraceAnnotation("pixie.embed"):
            entry.buf.fill(0)
            for i, (_, p) in enumerate(items):
                H, W = p.hw
                entry.buf[i, :H, :W] = p.payload
        with TraceAnnotation("pixie.ship"):
            if self.ingest == "async":
                frames = jnp.array(entry.buf, copy=True)
                entry.pending = frames
            else:
                frames = jnp.asarray(entry.buf)
        return frames

    def _ship_sharded_frames(self, mesh, n_tile: int, Hb: int, Wb: int,
                             dtype, items) -> jnp.ndarray:
        """Per-device canvas embed + ship for sharded async fused
        dispatches: each mesh device gets its OWN pooled host buffer
        (keyed by the device -- i.e. by its 2-D ``(app, rows)`` placement
        -- in :meth:`_canvas`), its shard of the tenant frames is embedded
        there, and the shards are shipped independently with
        ``jax.device_put`` -- so per-shard ingest overlaps across devices
        instead of serializing through one whole-batch canvas whose
        single pending transfer gates every shard's next fill.  On a 1-D
        mesh the buffer is ``[n_tile/k, Hb, Wb]`` (the app shard); on a
        2-D mesh it is ``[n_tile/app, Hb/rows, Wb]`` -- device ``(i, j)``
        fills app shard i's j-th row band, the row split the dispatch
        executable shards over (``Hb`` was pre-rounded to a band
        multiple, see :meth:`_dispatch_fused`).  The shards are assembled
        into ONE mesh-sharded global array
        (``make_array_from_single_device_arrays`` over the plan's mesh,
        spec ``P(app)`` / ``P(app, rows)`` -- exactly the layout the
        shard_map executable expects, so jit inserts no resharding copy).
        Bitwise-identical to the single-canvas path.

        CPU devices ship a private copy (``jnp.array(copy=True)``) for the
        same reason :meth:`_dispatch_fused`'s unsharded path does: a
        zero-copy aliased device_put would let the pooled buffer's next
        ``fill(0)`` race still-unforced lazy outputs.  Real accelerators
        copy host->HBM by construction and skip the extra hop."""
        from repro.parallel.sharding import frame_sharding
        grid2d = mesh.devices if mesh.devices.ndim == 2 else (
            mesh.devices[:, None]
        )
        app_n, rows_n = grid2d.shape
        shard_n = n_tile // app_n
        band = Hb // rows_n
        entries = [[self._canvas((shard_n, band, Wb), dtype, device=d)
                    for d in row] for row in grid2d]
        with TraceAnnotation("pixie.embed"):
            for row in entries:
                for e in row:
                    e.buf.fill(0)
            for i, (_, p) in enumerate(items):
                H, W = p.hw
                ai, slot = i // shard_n, i % shard_n
                for rj in range(rows_n):
                    h = min(H - rj * band, band)
                    if h > 0:
                        entries[ai][rj].buf[slot, :h, :W] = (
                            p.payload[rj * band:rj * band + h]
                        )
        shards = []
        with TraceAnnotation("pixie.ship"):
            for ai in range(app_n):
                for rj in range(rows_n):
                    e, d = entries[ai][rj], grid2d[ai, rj]
                    if d.platform == "cpu":
                        shard = jax.device_put(jnp.array(e.buf, copy=True),
                                               d)
                    else:
                        shard = jax.device_put(e.buf, d)
                    e.pending = shard
                    shards.append(shard)
        return jax.make_array_from_single_device_arrays(
            (n_tile, Hb, Wb), frame_sharding(mesh), shards,
        )

    def _fused_unpack(self, hws: Tuple[Tuple[int, int], ...], Hb: int, Wb: int):
        """Jit-once group unpack for async fused dispatches:
        ``ys [n_tile, K, Hb*Wb] -> tuple of [H, W] / [K, H, W]`` lazy
        outputs in item order, as a single device computation."""
        key = (hws, Hb, Wb)
        fn = self._unpack_fns.get(key)
        if fn is None:
            def unpack(ys):
                outs = []
                for i, (H, W) in enumerate(hws):
                    y = ys[i].reshape(-1, Hb, Wb)[:, :H, :W]
                    outs.append(y[0] if y.shape[0] == 1 else y)
                return tuple(outs)

            fn = jax.jit(unpack)
            self._unpack_fns.put(key, fn)
        return fn

    def _packed_unpack(self, batches: Tuple[int, ...],
                       hws: Tuple[Optional[Tuple[int, int]], ...]):
        """Jit-once group unpack for async unfused dispatches:
        ``ys [n_tile, K, batch] -> tuple`` of per-item ``[K, b]`` (or
        ``[H, W]`` / ``[K, H, W]`` for imaged items) lazy outputs -- one
        device computation, same rationale as :meth:`_fused_unpack`."""
        key = ("packed", batches, hws)
        fn = self._unpack_fns.get(key)
        if fn is None:
            def unpack(ys):
                outs = []
                for i, (b, hw) in enumerate(zip(batches, hws)):
                    y = ys[i, :, :b]
                    if hw is not None:
                        H, W = hw
                        y = y[:, : H * W].reshape(-1, H, W)
                        y = y[0] if y.shape[0] == 1 else y
                    outs.append(y)
                return tuple(outs)

            fn = jax.jit(unpack)
            self._unpack_fns.put(key, fn)
        return fn

    # -- batched execution ----------------------------------------------------

    def _prepare(self, request: FleetRequest) -> _Prepared:
        t0 = time.perf_counter()
        grid = request.grid or self.default_grid
        if request.pipeline is not None:
            prepared = self._prepare_pipeline(request, grid)
            self.timings["pack_s"] += time.perf_counter() - t0
            return prepared
        cfg = self.config_for(request.app, grid)
        if request.image is not None:
            image = np.asarray(request.image)
            if image.ndim != 2:
                raise ValueError(f"image must be [H, W], got shape {image.shape}")
            hw = tuple(image.shape)
            if cfg.ingest is not None:
                # Fused path: keep the RAW frame; line-buffer formation
                # happens inside the batched dispatch at flush time.
                prepared = _Prepared(grid, cfg, "image", image, hw)
                self.timings["pack_s"] += time.perf_counter() - t0
                return prepared
            # No ingest plan (a channel is neither tap nor const): fall
            # back to host-side tap packing so the request still runs.
            taps = app_lib.stencil_inputs(jnp.asarray(image))
            feed = {k: v for k, v in taps.items() if k in cfg.input_order}
        else:
            hw = None
            feed = request.inputs
        x = interpreter.pack_inputs(cfg, feed, grid.dtype)
        if x.ndim != 2:
            raise ValueError(f"fleet needs flat [channels, batch] inputs, got {x.shape}")
        prepared = _Prepared(
            grid, cfg, "channels", interpreter.pad_channels(x, grid.num_inputs), hw
        )
        self.timings["pack_s"] += time.perf_counter() - t0
        return prepared

    def _prepare_pipeline(self, request: FleetRequest,
                          grid: GridSpec) -> _Prepared:
        """Validate + map a chained request at submit time.  Every stage
        must carry an ingest plan (the chain is fused ingest end to end);
        a depth-1 chain demotes to the plain "image" kind so it batches
        and caches exactly like an ``app=`` request."""
        chain = list(request.pipeline)
        if not chain:
            raise ValueError("pipeline= must name at least one stage")
        image = np.asarray(request.image)
        if image.ndim != 2:
            raise ValueError(f"image must be [H, W], got shape {image.shape}")
        hw = tuple(image.shape)
        cfgs = [self.config_for(app, grid) for app in chain]
        for cfg in cfgs:
            if cfg.ingest is None:
                raise ValueError(
                    f"pipeline stage {cfg.app_name!r} has no ingest plan "
                    f"(a channel is neither stencil tap nor const); chains "
                    f"need fused-ingest stages end to end"
                )
        spec = PipelineSpec.chain(cfgs, request.out_channels)
        if spec.depth == 1:
            # The final stage's out_channel never selects anything (every
            # executor returns all K output channels), so a depth-1 chain
            # IS a plain fused request -- same plan key, same caches.
            return _Prepared(grid, cfgs[0], "image", image, hw)
        return _Prepared(grid, cfgs[0], "pipeline", image, hw, spec=spec)

    def _dispatch_fused(
        self, plan: OverlayPlan,
        items: List[Tuple[int, _Prepared]], out: Dict[int, np.ndarray],
    ) -> None:
        """One fused dispatch: raw frames -> outputs, line buffers inside.

        ``plan`` carries the execution axes (backend/mesh/tiling): the
        resilient flush passes the fleet's primary plan normally and a
        degraded sibling from :func:`repro.core.plan.fallback_chain` when
        the primary's circuit breaker is open -- same operands, same
        bitwise outputs, different executable.

        Frames are embedded top-left into one zero canvas [n_tile, Hb, Wb]
        (pow-2-bucketed sides, app axis rounded to batch_tile; reused from
        the canvas pool) on the HOST -- the dispatch is the only device
        operation.  The zero canvas right/below a frame is read by edge
        taps exactly like ``stencil_inputs``'s zero border, so the [H, W]
        slice of the output is bitwise identical to the unfused path.

        Under async ingest the canvas is shipped with ``jax.device_put``
        (NOT blocked on: the pool's depth-2 rotation defers that wait to
        the buffer's next reuse, by which time the copy is long done --
        see :class:`_PooledCanvas`), the executable *donates* it, and
        outputs are sliced lazily by one jitted group computation instead
        of materialized: the caller's first host read forces them, so
        packing of the next flush overlaps this flush's device execution.
        """
        t0 = time.perf_counter()
        fn = self.overlay_executable(plan)
        grid, radius = plan.grid, plan.radius
        n = len(items)
        n_tile = round_up(n, self._app_tile)
        Hb = pow2_bucket(max(p.hw[0] for _, p in items), self.min_image_side)
        Wb = pow2_bucket(max(p.hw[1] for _, p in items), self.min_image_side)
        if plan.mesh.rows > 1:
            # Row-sharded plans band-split Hb across the rows axis: round
            # it to a whole number of radius-floored bands so the sharded
            # ship path and the executable's in-spec agree on the band
            # split and the executable's own row padding is a no-op.
            Hb = row_band(Hb, plan.mesh.rows, radius) * plan.mesh.rows
        configs = [p.cfg for _, p in items]
        # Tile padding on the app axis: replay config[0] on a zero frame
        # of extent (0, 0), which the megakernel does not compute.
        configs += [configs[0]] * (n_tile - n)
        self.stats.padded_app_slots += n_tile - n
        self.stats.partial_tile_dispatches += 1 if n < n_tile else 0

        with TraceAnnotation("pixie.bank"):
            stacked, ingests = self._stacked_bank(grid, configs, fused=True)
            hw = _extents(items, n_tile)
        frames = self._ship_frames(fn.mesh, n_tile, Hb, Wb, grid.dtype, items)
        # The canvas embed + bank build + ship above are host-side pack
        # work; the overlay execution below is not.
        self.timings["pack_s"] += time.perf_counter() - t0
        self._pre_dispatch(plan, items)
        steps, live = self._kernel_steps(fn, radius, hw, Hb, Wb)
        with TraceAnnotation("pixie.execute", steps=steps, live=live):
            ys = self._execute(fn, stacked, ingests, hw, frames)
        ys = self._corrupt_outputs(plan, items, ys)
        self.stats.output_devices = len(ys.sharding.device_set)
        self.stats.kernel_steps += steps
        self.stats.kernel_steps_live += live
        self.stats.dispatches += 1
        self.stats.fused_dispatches += 1
        self.stats.stamp_dispatch(fn.plan, f"n{n_tile}x{Hb}x{Wb}")
        self.stats.executed += n
        self._unpack_frames(ys, items, Hb, Wb, out)

    def _dispatch_pipeline(
        self, plan: OverlayPlan,
        items: List[Tuple[int, _Prepared]], out: Dict[int, np.ndarray],
    ) -> None:
        """One chained dispatch: raw frames -> final-stage outputs, every
        intermediate device-resident.

        Frames embed, bucket and tile exactly like :meth:`_dispatch_fused`
        (same pow-2 canvas, same app-tile rounding, same async canvas
        pool/ship/lazy-unpack machinery) -- the chain only changes the
        executable (a pipeline :class:`OverlayPlan` keyed ``pipe{hash}``)
        and adds two operands: the per-stage settings banks (stacked per
        stage through the same bank cache single-stage dispatches use) and
        the per-app true frame extents ``hw`` that executors use to
        re-mask intermediates.  Padded app slots replay item 0's chain on
        a zero frame of extent (0, 0) and are sliced off -- outputs are
        bitwise identical to per-stage sequential flushes.

        ``plan`` arrives pre-built (the app-tile-padded spec tuple IS a
        plan axis), normally the primary from :meth:`_primary_plan`, or a
        degraded fallback sibling when the primary's breaker is open."""
        t0 = time.perf_counter()
        grid = plan.grid
        fn = self.overlay_executable(plan)
        n = len(items)
        n_tile = len(plan.pipeline)
        specs = list(plan.pipeline)
        radii = specs[0].radii
        Hb = pow2_bucket(max(p.hw[0] for _, p in items), self.min_image_side)
        Wb = pow2_bucket(max(p.hw[1] for _, p in items), self.min_image_side)
        if plan.mesh.rows > 1:
            Hb = row_band(Hb, plan.mesh.rows, plan.radius) * plan.mesh.rows
        self.stats.padded_app_slots += n_tile - n
        self.stats.partial_tile_dispatches += 1 if n < n_tile else 0

        with TraceAnnotation("pixie.bank"):
            stage_settings = []
            for si in range(len(radii)):
                stacked, ingests = self._stacked_bank(
                    grid, [s.stages[si].config for s in specs], fused=True
                )
                out_ch = jnp.asarray(
                    [s.stages[si].out_channel for s in specs], jnp.int32
                )
                stage_settings.append((stacked, ingests, out_ch))
            stage_settings = tuple(stage_settings)
            hw = _extents(items, n_tile)
        frames = self._ship_frames(fn.mesh, n_tile, Hb, Wb, grid.dtype, items)
        self.timings["pack_s"] += time.perf_counter() - t0
        self._pre_dispatch(plan, items)
        steps, live = self._kernel_steps(fn, sum(radii), hw, Hb, Wb)
        with TraceAnnotation("pixie.execute", steps=steps, live=live):
            ys = self._execute(fn, stage_settings, hw, frames)
        ys = self._corrupt_outputs(plan, items, ys)
        self.stats.output_devices = len(ys.sharding.device_set)
        self.stats.kernel_steps += steps
        self.stats.kernel_steps_live += live
        self.stats.dispatches += 1
        self.stats.fused_dispatches += 1
        self.stats.pipeline_dispatches += 1
        self.stats.stamp_dispatch(fn.plan, f"n{n_tile}x{Hb}x{Wb}")
        self.stats.executed += n
        self._unpack_frames(ys, items, Hb, Wb, out)

    @staticmethod
    def _kernel_steps(fn: OverlayExecutable, halo: int, hw: np.ndarray,
                      Hb: int, Wb: int) -> Tuple[int, int]:
        """``(steps, live)`` of a fused or chained dispatch's pallas
        megakernel over its ``[n_tile, Hb, Wb]`` canvas (``halo``: the
        plan's radius, a chain's total radius); ``(0, 0)`` where no one
        megakernel call covers the canvas (XLA plans, and mesh plans,
        whose kernels run per shard or per stage)."""
        plan = fn.plan
        if plan.backend != "pallas" or fn.mesh is not None:
            return 0, 0
        from repro.kernels.vcgra import grid_steps

        return grid_steps(plan.grid, halo, plan.tile_rows, hw, Hb, Wb)

    def _unpack_frames(self, ys, items: List[Tuple[int, _Prepared]],
                       Hb: int, Wb: int, out: Dict[int, Any]) -> None:
        """Each item's ``[H, W]`` (or ``[K, H, W]``) window of a fused or
        chained dispatch's canvas outputs ``ys [n_tile, K, Hb*Wb]``:
        sliced lazily by one jitted group program under async ingest,
        read to the host under sync."""
        with TraceAnnotation("pixie.unpack"):
            if self.ingest == "async":
                unpack = self._fused_unpack(tuple(p.hw for _, p in items),
                                            Hb, Wb)
                for (ticket, _), y in zip(items, unpack(ys)):
                    out[ticket] = y
                return
            for i, (ticket, p) in enumerate(items):
                H, W = p.hw
                y = np.asarray(ys[i]).reshape((-1, Hb, Wb))[:, :H, :W]
                out[ticket] = y[0] if y.shape[0] == 1 else y

    def _dispatch_packed(
        self, plan: OverlayPlan,
        items: List[Tuple[int, _Prepared]], out: Dict[int, np.ndarray],
    ) -> None:
        """One unfused dispatch over host-packed [channels, batch] inputs
        (named-channel requests and image apps without an ingest plan).
        Async ingest donates the channel stack and unpacks lazily, same as
        the fused path (the stack is rebuilt per flush, so donation is
        always safe).  ``plan`` carries the execution axes, exactly like
        :meth:`_dispatch_fused`."""
        t0 = time.perf_counter()
        grid = plan.grid
        fn = self.overlay_executable(plan)
        n = len(items)
        n_tile = round_up(n, self._app_tile)
        batch = pow2_bucket(max(p.payload.shape[-1] for _, p in items),
                            self.min_pixel_batch)
        configs = [p.cfg for _, p in items]
        xs = interpreter.pad_batches([p.payload for _, p in items], batch)
        # Tile padding on the app axis: replay config[0] on zero pixels.
        configs += [configs[0]] * (n_tile - n)
        xs += [jnp.zeros_like(xs[0])] * (n_tile - n)
        self.stats.padded_app_slots += n_tile - n
        self.stats.partial_tile_dispatches += 1 if n < n_tile else 0
        with TraceAnnotation("pixie.bank"):
            stacked = self._stacked_bank(grid, configs)
        xstack = jnp.stack(xs)
        self.timings["pack_s"] += time.perf_counter() - t0

        self._pre_dispatch(plan, items)
        with TraceAnnotation("pixie.execute"):
            ys = self._execute(fn, stacked, xstack)
        ys = self._corrupt_outputs(plan, items, ys)
        self.stats.output_devices = len(ys.sharding.device_set)
        self.stats.dispatches += 1
        self.stats.stamp_dispatch(fn.plan, f"n{n_tile}xb{batch}")
        self.stats.executed += n
        with TraceAnnotation("pixie.unpack"):
            if self.ingest == "async":
                unpack = self._packed_unpack(
                    tuple(p.payload.shape[-1] for _, p in items),
                    tuple(p.hw for _, p in items),
                )
                for (ticket, _), y in zip(items, unpack(ys)):
                    out[ticket] = y
                return
            for i, (ticket, p) in enumerate(items):
                y = np.asarray(ys[i, :, : p.payload.shape[-1]])
                if p.hw is not None:
                    H, W = p.hw
                    y = y[:, : H * W].reshape((-1, H, W))
                    y = y[0] if y.shape[0] == 1 else y
                out[ticket] = y

    def _execute(self, fn: OverlayExecutable, *args):
        """Run one dispatch executable.  On an unarmed fleet a failure
        that the executable also raises when it is only lowered and
        compiled for these operands' shapes is a build failure: it is
        re-raised as :class:`PlanBuildError` (never degraded, see
        :meth:`_dispatch_resilient`).  A failure that compiles cleanly is
        a runtime failure and keeps the ladder.  The re-lowering runs on
        the failure path only, at most once per flush for each plan and
        operand shapes."""
        try:
            return fn(*args)
        except Exception as exc:  # noqa: BLE001 -- build failure: PlanBuildError on every ticket; else the retry/fallback ladder
            if self._armed:
                raise
            specs = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=getattr(a, "sharding", None)),
                args,
            )
            verdict = (fn.plan.key(), str(specs))
            if verdict in self._flush_compiled:
                raise
            try:
                fn.lower(*specs).compile()
            except Exception:  # noqa: BLE001 -- fails again at build time: PlanBuildError on every ticket
                raise PlanBuildError(fn.plan.key(), exc) from exc
            self._flush_compiled.add(verdict)
            raise

    def _fail_batch(self, items: List[Tuple[int, _Prepared]],
                    exc: BaseException) -> None:
        """Fail every ticket of a work set with the same typed error."""
        for ticket, _ in items:
            self._failures[ticket] = exc
        while len(self._failures) > self.max_retained_results:
            self._failures.popitem(last=False)

    # -- resilient dispatch (PR 10) -------------------------------------------

    def _primary_plan(self, key: Tuple,
                      items: List[Tuple[int, _Prepared]]) -> OverlayPlan:
        """The fleet-configured plan of one flush group.  Pipeline groups
        bake their app-tile-padded spec tuple into the plan (padding is
        executable shape), so the plan is recomputed per work set during
        bisection."""
        grid = key[0]
        if key[1] == "image":
            return self.plan_for_dispatch(grid, fused=True, radius=key[2])
        if key[1] == "pipe":
            n_tile = round_up(len(items), self._app_tile)
            specs = [p.spec for _, p in items]
            specs += [specs[0]] * (n_tile - len(items))
            return self.plan_for_dispatch(grid, fused=True,
                                          pipeline=tuple(specs))
        return self.plan_for_dispatch(grid, fused=False)

    def _dispatch_plan(self, plan: OverlayPlan, kind: str,
                       items: List[Tuple[int, _Prepared]],
                       out: Dict[int, np.ndarray]) -> None:
        if kind == "image":
            self._dispatch_fused(plan, items, out)
        elif kind == "pipe":
            self._dispatch_pipeline(plan, items, out)
        else:
            self._dispatch_packed(plan, items, out)

    def _candidates(self, plan: OverlayPlan) -> Tuple[OverlayPlan, ...]:
        """``(primary, *fallback_chain)`` with the chain memoized per plan
        (plans are frozen/hashable; building the chain costs a few
        dataclass constructions we don't want per flush)."""
        chain = self._chain_cache.get(plan)
        if chain is None:
            chain = (plan, *fallback_chain(plan))
            self._chain_cache.put(plan, chain)
        return chain

    def _fault_tokens(self, plan: OverlayPlan,
                      items: List[Tuple[int, _Prepared]]) -> List[str]:
        """Context tokens a FaultSpec's ``match=`` is tested against:
        the plan key plus every rider's ticket and app name (bracketed so
        ``<ticket:1>`` never substring-matches ``<ticket:12>``)."""
        tokens = [f"plan:{plan.key()}"]
        for ticket, p in items:
            tokens.append(f"<ticket:{ticket}>")
            tokens.append(f"<app:{p.cfg.app_name}>")
        return tokens

    def _pre_dispatch(self, plan: OverlayPlan,
                      items: List[Tuple[int, _Prepared]]) -> None:
        """Fire the stall and dispatch hook points (no-op without an
        injector: one attribute check, the zero-overhead contract)."""
        if self.faults is None:
            return
        tokens = self._fault_tokens(plan, items)
        self.faults.fire("transfer_stall", tokens)
        self.faults.fire("dispatch", tokens)

    def _corrupt_outputs(self, plan: OverlayPlan,
                         items: List[Tuple[int, _Prepared]], ys):
        """Apply armed ``nan_output`` corruption to the dispatch's output
        batch (inexact dtypes only: integer fabrics cannot encode NaN, so
        the output guard scopes itself the same way)."""
        if self.faults is None:
            return ys
        if not jnp.issubdtype(jnp.asarray(ys).dtype, jnp.inexact):
            return ys
        slots = self.faults.corrupt_slots(
            [[f"<ticket:{t}>", f"<app:{p.cfg.app_name}>"] for t, p in items]
        )
        for i in slots:
            ys = ys.at[i].set(jnp.nan)
        return ys

    def _guard_outputs(self, got: Dict[int, Any],
                       items: List[Tuple[int, _Prepared]],
                       ) -> List[Tuple[int, _Prepared]]:
        """The NaN/Inf output guard: pops poisoned tickets out of ``got``
        and returns their work items (the resilient loop re-dispatches
        just those).  Float outputs only; forces async lazy outputs, which
        is why the guard defaults on only when faults are installed."""
        if not self._guard:
            return []
        bad = []
        for ticket, prep in items:
            y = got.get(ticket)
            if y is None:
                continue
            arr = np.asarray(y)
            if (np.issubdtype(arr.dtype, np.floating)
                    and not np.isfinite(arr).all()):
                bad.append((ticket, prep))
                del got[ticket]
        return bad

    def _quarantine(self, ticket: int, prep: _Prepared,
                    cause: Optional[BaseException]) -> None:
        """Fail ONE isolated request: record a QuarantinedError against
        its ticket (raised by result(), drained by front-ends via
        pop_failures) -- the batch it rode dispatches on without it."""
        self.stats.quarantined_requests += 1
        exc = QuarantinedError(ticket, app=prep.cfg.app_name, cause=cause)
        if cause is not None:
            exc.__cause__ = cause
        self._failures[ticket] = exc
        while len(self._failures) > self.max_retained_results:
            self._failures.popitem(last=False)

    def _dispatch_resilient(self, key: Tuple,
                            items: List[Tuple[int, _Prepared]],
                            out: Dict[int, np.ndarray]) -> None:
        """One flush group through the self-healing ladder:

        1. the primary plan, retried with deterministic backoff on
           *transient* failures (``RetryPolicy.should_retry``);
        2. on exhaustion/non-transient failure -- or when the primary's
           circuit breaker is open -- each plan of the fallback chain in
           turn (every step bitwise-equal by construction, each behind
           its own breaker);
        3. outputs through the NaN/Inf guard: clean tickets commit, and
           only the poisoned ones go around again;
        4. if EVERY plan fails the whole work set, bisect: halves recurse
           independently, so poison is isolated to exactly the offending
           request(s), whose tickets fail with QuarantinedError while all
           survivors dispatch normally.

        On an unarmed fleet a primary that fails to build or lower
        (:class:`PlanBuildError`) skips all of this: every ticket of the
        work set fails with that error and no fallback plan runs.

        Breaker successes are deferred to flush end (_settle_flush): a
        straggler flush converts them into breaker failures when the
        fleet is armed for it."""
        kind = key[1]
        primary = self._primary_plan(key, items)
        candidates = self._candidates(primary)
        last_exc: Optional[BaseException] = None
        tried_any = False
        for ci, cand in enumerate(candidates):
            br = self.breakers.breaker(cand.key())
            last_resort = ci == len(candidates) - 1 and not tried_any
            if not br.allow() and not last_resort:
                continue
            tried_any = True
            for attempt in range(self.retry.max_attempts):
                if attempt:
                    self.stats.retries += 1
                    time.sleep(self.retry.backoff_s(attempt - 1))
                got: Dict[int, Any] = {}
                try:
                    self._dispatch_plan(cand, kind, items, got)
                    bad = self._guard_outputs(got, items)
                except PlanBuildError as exc:
                    if ci == 0:
                        # Unarmed fleet, primary refused at build time:
                        # surface it on the whole batch, never degrade.
                        self._fail_batch(items, exc)
                        return
                    last_exc = exc
                    br.record_failure()
                    break
                except Exception as exc:  # noqa: BLE001 -- routed: retried here, then degraded down the fallback chain or quarantined to the offending ticket below
                    last_exc = exc
                    br.record_failure()
                    if self.retry.should_retry(exc):
                        continue
                    break
                if bad:
                    out.update(got)
                    self.stats.guard_failures += len(bad)
                    br.record_failure("nan_guard")
                    last_exc = PoisonedOutputError(
                        f"{len(bad)}/{len(items)} outputs of plan "
                        f"{cand.key()} failed the NaN/Inf guard"
                    )
                    if len(bad) < len(items):
                        # Survivors committed; the poisoned subset takes
                        # the whole ladder again from the primary.
                        self._dispatch_resilient(key, bad, out)
                        return
                    continue  # whole batch poisoned: burn a retry
                out.update(got)
                self._flush_successes.append((br, cand.key()))
                if ci:   # not the primary (by position: the memoized
                    # chain returns value-equal but distinct plan objects)
                    self.stats.fallback_dispatches += 1
                return
        if len(items) == 1:
            ticket, prep = items[0]
            self._quarantine(ticket, prep, last_exc)
            return
        mid = len(items) // 2
        self._dispatch_resilient(key, items[:mid], out)
        self._dispatch_resilient(key, items[mid:], out)

    def _settle_flush(self, dispatched: bool, flush_s: float) -> None:
        """Flush epilogue: feed the wall time to the HeartbeatMonitor and
        settle the deferred breaker successes -- a straggler flush counts
        against every plan it dispatched (when armed: faults/breakers/
        heartbeat explicitly installed), otherwise each plan records its
        success."""
        straggler = False
        if dispatched and self.heartbeat is not None:
            straggler = self.heartbeat.record(self.stats.dispatches, flush_s)
            if straggler:
                self.stats.straggler_flushes += 1
        punish = straggler and self._armed
        for br, _key in self._flush_successes:
            if punish:
                br.record_failure("straggler")
            else:
                br.record_success()
        self._flush_successes = []

    def pop_failures(self) -> Dict[int, BaseException]:
        """Drain per-ticket failures (QuarantinedError etc.) recorded by
        resilient flushes -- front-ends route each to its own JobHandle.
        Tickets not drained here raise from :meth:`result`."""
        if not self._failures:
            return {}
        failures = dict(self._failures)
        self._failures.clear()
        return failures

    def install_faults(self, faults) -> None:
        """Arm an injector after construction (the streaming front-end
        installs its injector into the fleet it owns).  Installing faults
        also arms the NaN/Inf output guard and the straggler->breaker
        coupling, same as passing ``faults=`` at construction."""
        self.faults = faults
        self._guard = True
        self._armed = True

    def cancel_pending(self) -> int:
        """Drop every submitted-but-unflushed request (no results, no
        failures recorded); returns how many were dropped.  The streaming
        supervisor calls this after a worker crash so a restarted worker
        never re-serves tickets whose handles were already failed."""
        n = len(self._pending)
        self._pending.clear()
        return n

    def pending_count(self) -> int:
        """Requests submitted but not yet flushed (the continuous-batching
        scheduler polls this to decide between waiting for a full tile and
        launching a partial one)."""
        return len(self._pending)

    def flush(self, limit: Optional[int] = None) -> Dict[int, np.ndarray]:
        """Run pending requests; one overlay dispatch per grid group
        (two when a group mixes fused image requests with named-channel
        requests).

        ``limit`` is the partial-tile hook for continuous-batching
        schedulers: only the oldest ``limit`` pending requests are
        dispatched (in submit order) and the rest stay queued for a later
        flush -- a deadline-pressed scheduler launches a partially-filled
        tile now without dragging every newly-arrived request into it.
        ``None`` keeps the drain-everything behavior.

        Per-flush latency stamps land in ``timings``: ``flush_started``
        (perf_counter at dispatch start, shared by every request in the
        flush -- front-ends split per-request queue wait from flush time
        with it) and ``flush_s`` (wall duration of this flush).  Under
        async ingest ``flush_s`` ends once the dispatch is *enqueued*, not
        when the device has served it; the device's part is read from the
        ``pixie.*`` profiler spans and the device trace.

        Returns {ticket: output}; image requests come back as [H, W] (or
        [num_outputs, H, W]), channel requests as [num_outputs, batch].
        Sync ingest returns eager numpy; async ingest returns lazy jax
        arrays (bitwise-identical values, forced on first host read) so
        the device keeps executing while the caller packs its next batch.
        """
        if limit is None or limit >= len(self._pending):
            pending, self._pending = self._pending, []
        else:
            if limit < 1:
                raise ValueError(f"flush limit must be >= 1, got {limit}")
            pending, self._pending = self._pending[:limit], self._pending[limit:]
        # Group by (grid, path): fused image groups additionally key on the
        # stencil radius, which fixes the tap-bank layout of the executable.
        groups: Dict[Tuple, List[Tuple[int, _Prepared]]] = {}
        for ticket, p in pending:
            if p.kind == "image":
                key = (p.grid, "image", p.cfg.ingest.radius)
            elif p.kind == "pipeline":
                # Chains batch together when their per-stage radii agree
                # (depth and radii are executable shape; the specs
                # themselves ride the plan as per-tenant settings).
                key = (p.grid, "pipe", p.spec.radii)
            else:
                key = (p.grid, "channels")
            groups.setdefault(key, []).append((ticket, p))

        out: Dict[int, np.ndarray] = {}
        t0 = time.perf_counter()
        self.timings["flush_started"] = t0
        self._flush_successes = []
        self._flush_compiled = set()
        for key, items in groups.items():
            self._dispatch_resilient(key, items, out)
        flush_s = time.perf_counter() - t0
        self.timings["flush_s"] = flush_s
        self._settle_flush(bool(groups), flush_s)
        self._results.update(out)
        while len(self._results) > self.max_retained_results:
            self._results.popitem(last=False)
        return out

    def run_many(self, requests: Sequence[FleetRequest]) -> List[np.ndarray]:
        """submit() + flush() convenience; outputs in request order (and
        released from retention, so nothing stays behind).  Consumes the
        flush() return value directly -- correct for any batch size, even
        beyond ``max_retained_results``."""
        tickets = [self.submit(r) for r in requests]
        outs = self.flush()
        failures = self.pop_failures()
        for t in tickets:
            self.discard(t)
        for t in tickets:
            if t in failures:
                raise failures[t]
        return [outs[t] for t in tickets]
