"""OverlayPlan: the unified compile/dispatch pipeline for the overlay.

The paper's value proposition is ONE virtual overlay that many
applications reconfigure cheaply at runtime; the runtime realizes it, but
the "compile an overlay" surface had grown into a 2x2x2 matrix of factory
functions (``make_*_overlay_fn`` x ``backend``) that every layer
re-plumbed by hand.  This module collapses that matrix into a single
plan -> compile -> execute pipeline:

  OverlayPlan      a frozen, hashable description of one dispatch: grid
                   structure, fused-vs-channel ingest (+ tap radius),
                   single-vs-batched app axis, execution backend, device
                   placement.  It is THE cache key: the fleet's overlay
                   LRU, benchmark JSON and stats all name executables by
                   their plan.
  compile_plan     the one entrypoint: plan -> OverlayExecutable.  Looks
                   the executor builder up in a registry (XLA builders
                   registered here; the Pallas megakernels register
                   themselves from ``kernels/vcgra/ops.py``), wraps it
                   with app-axis mesh sharding when the plan asks for
                   devices > 1, and jits once.
  OverlayExecutable  the compiled artifact: callable with the plan-shaped
                   operands, carries its plan and (when sharded) mesh.

Device placement is a structured :class:`repro.parallel.axes.MeshSpec`:
``MeshSpec(app=k)`` shards the app (N) axis of a *batched* plan across k
local devices via shard_map (``parallel/axes.build_mesh`` /
``shard_apps``) -- the app axis is embarrassingly parallel (each tenant's
flat-gather offsets are local to its own rows), so the sharded result is
bitwise identical to the single-device run.  ``MeshSpec(app=k, rows=m)``
additionally shards fused frames *spatially* over a 2-D ``(app, rows)``
mesh: each row shard owns a contiguous band of pixel rows and exchanges
the radius-wide seam halo with its neighbours
(``parallel/axes.shard_apps_rows``), then runs the unchanged per-shard
executor -- still bitwise identical, because a haloed band reads exactly
like a short frame whose border pixels are real neighbour rows.  When
the host has fewer devices than the spec asks for, compilation falls
back to the single-device executable (same bits, same plan key).  N not
divisible by the app width -- and H not divisible into radius-deep row
bands -- is padded inside the executable and sliced back off.  The
deprecated bare device-count kwarg survives as a DeprecationWarning shim
meaning ``MeshSpec(app=k)``.

The legacy ``interpreter.make_*_overlay_fn`` factories survive as thin
deprecated shims delegating here.
"""

from __future__ import annotations

import dataclasses
import hashlib
import warnings
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import interpreter
from repro.core.bitstream import VCGRAConfig
from repro.core.grid import GridSpec
from repro.core.ingest import INGEST_MODES, check_ingest  # noqa: F401
from repro.core.tiling import TILE_AUTO, check_tile_rows, row_band
from repro.parallel.axes import (
    MeshSpec, build_mesh, shard_apps, shard_apps_rows, shard_pipeline_rows,
)

#: Execution backends a plan may name (re-exported from the interpreter,
#: which owns the validation shared with the fleet and the front-end).
BACKENDS = interpreter.BACKENDS


# -- the pipeline axis ---------------------------------------------------------


def _config_digest(cfg: VCGRAConfig) -> str:
    """Canonical content digest of one stage's settings: everything that
    shapes the traced executable (grid structure name, opcodes, mux
    selects, output taps, ingest production rules, const coefficients).
    sha1 over raw bytes -- deterministic across processes, unlike
    ``hash()`` under PYTHONHASHSEED randomization -- because pipeline
    digests end up in plan keys that bench JSON and stats compare across
    runs.  ``VCGRAConfig`` itself stays an unfrozen builder object; the
    digest is what makes a stage *hashable* without freezing it."""
    h = hashlib.sha1()
    h.update(cfg.grid_name.encode())
    for ops_lvl in cfg.opcodes:
        h.update(np.asarray(ops_lvl, np.int32).tobytes())
    for sel_lvl in cfg.selects:
        h.update(np.asarray(sel_lvl, np.int32).tobytes())
    h.update(np.asarray(cfg.out_sel, np.int32).tobytes())
    h.update(repr(tuple(cfg.input_order)).encode())
    h.update(
        repr(sorted((str(k), float(v)) for k, v in cfg.const_values.items()))
        .encode()
    )
    ing = cfg.ingest
    if ing is not None:
        h.update(str(int(ing.radius)).encode())
        h.update(np.asarray(ing.tap_sel, np.int32).tobytes())
        h.update(np.asarray(ing.const_vals, np.float64).tobytes())
    return h.hexdigest()


@dataclasses.dataclass(frozen=True, eq=False)
class PipelineStage:
    """One stage of a pipeline chain: a mapped app config plus which of
    its output channels feeds the next stage's ingest taps.

    ``config`` must carry an :class:`~repro.core.ingest.IngestPlan` (every
    stage eats a raw frame -- the previous stage's device-resident
    intermediate); its radius IS the stage's tap radius.  Use
    :meth:`at_radius` to re-plan a stage at a different radius (e.g. a
    pointwise threshold stage on a radius-0 bank).  ``out_channel`` on the
    LAST stage is forwarding metadata with nothing to feed; the chain
    returns that stage's full ``[K, H*W]`` output like any fused dispatch.

    Hash/eq ride a content digest (:func:`_config_digest`), so stages slot
    into frozen plans without freezing ``VCGRAConfig``.
    """

    config: VCGRAConfig
    out_channel: int = 0

    def __post_init__(self):
        if self.config.ingest is None:
            raise ValueError(
                f"pipeline stage {self.config.app_name!r} has no ingest "
                "plan (a channel is neither a stencil tap nor a const); "
                "every stage must eat a raw frame"
            )
        object.__setattr__(self, "out_channel", int(self.out_channel))
        if not 0 <= self.out_channel < len(self.config.out_sel):
            raise ValueError(
                f"out_channel={self.out_channel} out of range for "
                f"{self.config.app_name!r} ({len(self.config.out_sel)} "
                "output channels)"
            )
        object.__setattr__(
            self,
            "_digest",
            hashlib.sha1(
                f"{_config_digest(self.config)}|out{self.out_channel}".encode()
            ).hexdigest(),
        )

    @property
    def digest(self) -> str:
        return self._digest

    @property
    def radius(self) -> int:
        return int(self.config.ingest.radius)

    def at_radius(self, radius: int) -> "PipelineStage":
        """The same stage re-planned against a different tap-bank radius
        (see :meth:`IngestPlan.at_radius`).  The returned config's
        ``cache_key`` is re-suffixed so the fleet's radius-keyed settings
        banks never alias the original."""
        if int(radius) == self.radius:
            return self
        cfg = dataclasses.replace(
            self.config, ingest=self.config.ingest.at_radius(radius)
        )
        if cfg.cache_key is not None:
            cfg.cache_key = f"{cfg.cache_key}@r{int(radius)}"
        return PipelineStage(cfg, self.out_channel)

    def __hash__(self):
        return hash(self._digest)

    def __eq__(self, other):
        return (
            isinstance(other, PipelineStage) and self._digest == other._digest
        )


@dataclasses.dataclass(frozen=True, eq=False)
class PipelineSpec:
    """A frozen, hashable ordered chain of :class:`PipelineStage`s: the
    pipeline axis of ONE app slot.  Stage *i*'s selected output channel
    feeds stage *i+1*'s ingest taps as a raw frame; intermediates never
    leave the device (no unpack/repack, no host hop).  Linear chains
    today -- the degenerate DAG; the stage tuple is the topological order
    a richer DAG would serialize to."""

    stages: Tuple[PipelineStage, ...]

    def __post_init__(self):
        stages = tuple(self.stages)
        if not stages:
            raise ValueError("a pipeline needs at least one stage")
        gname = stages[0].config.grid_name
        for s in stages[1:]:
            if s.config.grid_name != gname:
                raise ValueError(
                    "every stage of a pipeline runs on ONE overlay grid "
                    f"(reconfigured between stages): {s.config.grid_name!r} "
                    f"!= {gname!r}"
                )
        object.__setattr__(self, "stages", stages)
        h = hashlib.sha1()
        for s in stages:
            h.update(s.digest.encode())
        object.__setattr__(self, "_digest", h.hexdigest())

    @property
    def depth(self) -> int:
        return len(self.stages)

    @property
    def radii(self) -> Tuple[int, ...]:
        return tuple(s.radius for s in self.stages)

    @property
    def total_radius(self) -> int:
        """Sum of stage radii: the total row pad one output pixel's
        provenance reaches back through the whole chain -- what the Pallas
        megakernel pads its DMA slabs by."""
        return sum(self.radii)

    @property
    def digest(self) -> str:
        return self._digest

    @staticmethod
    def chain(
        configs: Sequence[VCGRAConfig],
        out_channels: Optional[Sequence[int]] = None,
    ) -> "PipelineSpec":
        """Build a linear chain from mapped configs (+ optional per-stage
        forwarded output channels, default 0)."""
        cfgs = list(configs)
        chans = list(out_channels) if out_channels is not None else [0] * len(cfgs)
        if len(chans) != len(cfgs):
            raise ValueError(
                f"{len(chans)} out_channels for {len(cfgs)} stages"
            )
        return PipelineSpec(
            tuple(PipelineStage(c, ch) for c, ch in zip(cfgs, chans))
        )

    def __hash__(self):
        return hash(self._digest)

    def __eq__(self, other):
        return (
            isinstance(other, PipelineSpec) and self._digest == other._digest
        )


def pipeline_digest(specs: Sequence[PipelineSpec]) -> str:
    """Combined digest of one dispatch's per-app-slot pipeline specs --
    the ``pipe{...}`` segment of the plan key."""
    h = hashlib.sha1()
    for s in specs:
        h.update(s.digest.encode())
    return h.hexdigest()


@dataclasses.dataclass(frozen=True)
class OverlayPlan:
    """A frozen, hashable description of one overlay dispatch.

    Axes (the former factory-function matrix, now data):

    * ``grid``     the overlay structure (trace-time constants);
    * ``batched``  single app (``[C, batch]`` channels / ``[H, W]``
      frame) vs N stacked tenants (leading app axis on every operand);
    * ``fused``    raw-frame ingest (line buffers formed inside the
      dispatch, tap bank of ``radius``) vs pre-packed channels;
    * ``backend``  "xla" (the hand-lowered interpreter, the bitwise
      oracle) or "pallas" (the VCGRA megakernels);
    * ``mesh``     the :class:`MeshSpec` device placement --
      ``MeshSpec()`` is single-device, ``app`` > 1 shards the app axis
      (requires ``batched``), ``rows`` > 1 row-bands fused frames with
      seam halo exchange (requires ``batched`` AND ``fused``; unfused
      channels carry no row structure to band).  The deprecated bare
      device-count kwarg still constructs (with a DeprecationWarning) and
      means ``MeshSpec(app=k)`` -- same plan, same key, same cache entry;
    * ``tile_rows``  pixel-axis row tiling of the fused executors: None
      (untiled -- the whole padded frame and tap bank are resident at
      once), an int (rows per tile, each tile carrying a radius-wide row
      halo) or ``tiling.TILE_AUTO`` (the VMEM budget heuristic picks at
      trace time from the static frame shape).  Fused plans only --
      the unfused path has no tap bank and already tiles its flat pixel
      axis.  All values are bitwise-identical.  On ``backend="pallas"``
      the tiling lowers to the in-kernel double-buffered HBM->VMEM DMA
      pipeline (kernels/vcgra/vcgra_kernel.py) -- a kernel-internal
      lowering choice, NOT a plan axis: keys and cache entries are
      unchanged from the pre-DMA layout;
    * ``ingest``   "sync" (pack, dispatch, materialize in order) or
      "async" (the dispatch's frame/channel operand is *donated*, so the
      fleet's double-buffered pipeline can ship pooled canvases with
      ``jax.device_put`` and overlap packing flush k+1 with executing
      flush k).  Bitwise-identical; only buffer lifetime differs.

    Two dispatches with equal plans share one compiled executable; any
    layer that caches executables keys on the plan itself.
    """

    grid: GridSpec
    batched: bool = False
    fused: bool = False
    radius: Optional[int] = None     # tap-bank radius; fused plans only
    backend: str = "xla"
    mesh: MeshSpec = MeshSpec()
    tile_rows: Union[int, str, None] = None  # fused plans only
    ingest: str = "sync"
    #: The pipeline axis: one :class:`PipelineSpec` per app slot of the
    #: batched dispatch (all sharing depth and per-stage radii -- that is
    #: executable shape; the per-stage *settings* differ per slot).
    #: Depth-1 "chains" canonicalize to ``pipeline=None`` + the stage's
    #: radius at construction, so they ARE the existing single-stage
    #: batched fused plan: same key, same hash, same cache entry.
    pipeline: Optional[Tuple[PipelineSpec, ...]] = None
    #: Deprecated spelling of ``mesh=MeshSpec(app=k)`` (the pre-2-D bare
    #: device-count kwarg).  Not a field: it maps onto ``mesh`` at
    #: construction, so both spellings are ONE plan and ONE cache entry.
    devices: dataclasses.InitVar[Optional[int]] = None

    def __post_init__(self, devices):
        if devices is not None:
            d = int(devices)
            if d < 1:
                raise ValueError(f"devices must be >= 1, got {devices}")
            if self.mesh != MeshSpec():
                raise ValueError(
                    "pass mesh=MeshSpec(...) or the deprecated bare device "
                    "count, not both"
                )
            warnings.warn(
                "the bare device-count kwarg of OverlayPlan is deprecated: "
                f"pass mesh=MeshSpec(app={d}) instead",
                DeprecationWarning,
                stacklevel=3,
            )
            object.__setattr__(self, "mesh", MeshSpec(app=d))
        interpreter.check_backend(self.backend)
        check_ingest(self.ingest)
        if self.pipeline is not None:
            specs = tuple(self.pipeline)
            if not specs or not all(
                isinstance(s, PipelineSpec) for s in specs
            ):
                raise ValueError(
                    "pipeline must be a non-empty sequence of PipelineSpec "
                    "(one per app slot)"
                )
            ref = specs[0]
            for s in specs[1:]:
                if s.radii != ref.radii:
                    raise ValueError(
                        "every app slot of a pipeline dispatch must share "
                        f"the stage structure: radii {s.radii} != {ref.radii} "
                        "(depth and per-stage radii are executable shape)"
                    )
            for s in specs:
                for st in s.stages:
                    if st.config.grid_name != self.grid.name:
                        raise ValueError(
                            "pipeline stage mapped on grid "
                            f"{st.config.grid_name!r} cannot run on plan "
                            f"grid {self.grid.name!r}"
                        )
            if not self.batched:
                raise ValueError(
                    "a pipeline plan is a batched fused dispatch (single "
                    "chains run as N=1); set batched=True"
                )
            if self.radius is not None:
                raise ValueError(
                    "radius is derived from the pipeline's stages; don't "
                    "pass both"
                )
            object.__setattr__(self, "fused", True)
            if ref.depth == 1:
                # Depth-1 canonicalization: a single-stage "chain" IS the
                # existing batched fused plan -- hash, key and cache entry
                # all land on the pre-pipeline population.
                object.__setattr__(self, "pipeline", None)
                object.__setattr__(self, "radius", ref.radii[0])
            else:
                object.__setattr__(self, "pipeline", specs)
                # The plan-level radius of a chain is the max stage radius:
                # it governs the rows-mesh band floor (every per-stage halo
                # exchange must stay single-hop).  Full identity lives in
                # the key's pipe{digest} segment.
                object.__setattr__(self, "radius", max(ref.radii))
        if self.fused:
            # Canonical key: a fused plan always names its radius.
            object.__setattr__(
                self, "radius", 1 if self.radius is None else int(self.radius)
            )
            if self.radius < 0:
                raise ValueError(f"fused plan needs radius >= 0, got {self.radius}")
        elif self.radius is not None:
            raise ValueError(
                f"radius={self.radius} is meaningless for an unfused plan "
                "(the tap bank only exists on the fused ingest path)"
            )
        if self.tile_rows is not None:
            if not self.fused:
                raise ValueError(
                    f"tile_rows={self.tile_rows!r} is meaningless for an "
                    "unfused plan (pre-packed channels carry no row "
                    "structure to halo-tile; the pixel axis is already "
                    "block-tiled by the executors)"
                )
            # Canonical key: explicit tile heights are ints.
            object.__setattr__(self, "tile_rows", check_tile_rows(self.tile_rows))
        if not isinstance(self.mesh, MeshSpec):
            raise ValueError(
                f"mesh must be a MeshSpec, got {self.mesh!r}"
            )
        if self.mesh.app > 1 and not self.batched:
            raise ValueError(
                "an app-axis mesh width > 1 shards the app (N) axis, which "
                "only batched plans have; set batched=True or app=1"
            )
        if self.mesh.rows > 1 and not (self.batched and self.fused):
            raise ValueError(
                "a rows-axis mesh width > 1 band-shards the pixel rows of "
                "fused frames, which only batched fused plans have (pre-"
                "packed channels carry no row structure); set fused=True "
                "or rows=1"
            )

    def key(self) -> str:
        """Compact human-readable identity, used by stats stamping and
        bench JSON (``FleetStats.dispatch_plans``).  The tile/ingest
        segments appear only off their defaults, and the rows segment only
        when the mesh is 2-D, so PR 4-era keys are stable --
        ``MeshSpec(app=2)`` stamps the exact old ``dev2`` key and reuses
        that executable population."""
        parts = [
            self.grid.name,
            "batched" if self.batched else "single",
            f"fused:r{self.radius}" if self.fused else "channels",
            self.backend,
            f"dev{self.mesh.app}",
        ]
        if self.pipeline is not None:
            # Depth>1 only (depth-1 canonicalized to pipeline=None), so
            # every pre-pipeline key -- and its cache entry -- survives.
            parts.append(f"pipe{pipeline_digest(self.pipeline)[:12]}")
        if self.mesh.rows > 1:
            parts.append(f"rows{self.mesh.rows}")
        if self.tile_rows is not None:
            parts.append(f"tile:{self.tile_rows}")
        if self.ingest != "sync":
            parts.append(self.ingest)
        return "|".join(parts)


def replace_plan(plan: OverlayPlan, **overrides: Any) -> OverlayPlan:
    """``dataclasses.replace`` that is safe for pipeline plans.

    ``__post_init__`` derives ``fused``/``radius`` from the pipeline
    stages and rejects passing both, so a naive ``replace`` (which
    re-passes every field) raises on any pipeline plan.  Reconstruct from
    the orthogonal axes instead; plain plans go through ``replace``."""
    if plan.pipeline is not None:
        fields = dict(
            grid=plan.grid, batched=True, pipeline=plan.pipeline,
            backend=plan.backend, mesh=plan.mesh,
            tile_rows=plan.tile_rows, ingest=plan.ingest,
        )
        fields.update(overrides)
        return OverlayPlan(**fields)
    return dataclasses.replace(plan, **overrides)


def fallback_chain(plan: OverlayPlan) -> Tuple[OverlayPlan, ...]:
    """The graceful-degradation ladder of ``plan``, most- to
    least-capable: each step strips ONE risky axis while preserving the
    request-shaped axes (grid, fusion, radius/pipeline, ingest), so any
    step can serve the exact same dispatch operands.

      1. ``backend="pallas"`` -> ``"xla"`` (the bitwise oracle);
      2. 2-D ``MeshSpec(app=a, rows=r)`` -> ``app_only()`` (drop the
         halo-exchanging rows axis);
      3. ``MeshSpec(app=a)`` -> single device;
      4. ``tile_rows`` -> ``None`` (untiled pixel axis).

    Every step is bitwise-equal to the primary by the parity guarantees
    each axis carries (enforced in CI), so a circuit breaker can degrade
    dispatch-by-dispatch without changing results.  Each entry is a
    distinct :class:`OverlayPlan` -- i.e. just another plan-cache key, so
    fallback executables cost one compile each, ever."""
    chain: List[OverlayPlan] = []
    cur = plan

    def step(**overrides: Any) -> None:
        nonlocal cur
        nxt = replace_plan(cur, **overrides)
        if nxt != cur:
            chain.append(nxt)
            cur = nxt

    if cur.backend != "xla":
        step(backend="xla")
    if cur.mesh.rows > 1:
        step(mesh=cur.mesh.app_only())
    if cur.mesh.app > 1:
        step(mesh=MeshSpec())
    if cur.tile_rows is not None:
        step(tile_rows=None)
    return tuple(chain)


class OverlayExecutable:
    """The compiled artifact of one :class:`OverlayPlan`.

    Callable with the plan-shaped operands:

      batched=False, fused=False   fn(config_arrays, x)
      batched=False, fused=True    fn(config_arrays, ingest_arrays, image)
      batched=True,  fused=False   fn(stacked_configs, xs)
      batched=True,  fused=True    fn(stacked_configs, stacked_ingests, hw, images)
      pipeline (depth > 1)         fn(stage_settings, hw, images)

    ``hw`` is int32 [N, 2] of per-app true ``(rows, cols)`` inside the
    (possibly bucketed) canvas (a batched fused dispatch also takes None:
    the whole canvas).  Every executor returns zeros outside it, and the
    pallas megakernels compute nothing there; a padded app slot carries
    ``(0, 0)``.  Pipeline operands: ``stage_settings`` is one
    ``(stacked_configs, stacked_ingests, out_ch)`` triple per stage
    (``out_ch`` int32 [N]); everything outside ``hw`` is zeroed between
    stages so the fused chain matches the staged oracle bitwise.  The
    single-device XLA executor is *specialized at trace time* from the
    plan's static configs and ignores the settings operands (the plan is
    the source of truth -- callers must pass settings matching it, which
    the fleet does by construction); mesh-sharded and Pallas executors
    consume them as runtime data.  One signature either way.

    ``mesh`` is the device mesh the dispatch is sharded over (1-D for
    app-only specs, 2-D for row-banded ones), or None for the
    single-device path (including the fallback when the host could not
    honor ``plan.mesh``).
    """

    def __init__(self, plan: OverlayPlan, fn: Callable, mesh=None):
        self.plan = plan
        self._fn = fn
        self.mesh = mesh
        # Forward jit-cache introspection when the running jax has it
        # (fleet.overlay_executable_count uses it for compile-once asserts).
        sizer = getattr(fn, "_cache_size", None)
        if callable(sizer):
            self._cache_size = sizer

    def __call__(self, *args):
        return self._fn(*args)

    def lower(self, *args):
        """AOT lowering passthrough (``Pixie.compile_overlay`` times it)."""
        return self._fn.lower(*args)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"OverlayExecutable({self.plan.key()})"


# -- executor registry ---------------------------------------------------------

ExecutorBuilder = Callable[[OverlayPlan], Callable]
_EXECUTOR_BUILDERS: Dict[Tuple[str, bool, bool], ExecutorBuilder] = {}


def register_executor(backend: str, *, batched: bool, fused: bool):
    """Register the executor builder for one (backend, batched, fused)
    cell of the plan matrix.  The builder takes the plan and returns an
    (unjitted or jitted) callable with the plan-shaped operands;
    ``compile_plan`` applies sharding and the outer jit.  The XLA cells
    are registered below; ``kernels/vcgra/ops.py`` registers the pallas
    cells on import so the kernel package owns its own dispatch wiring
    instead of being special-cased here."""

    def deco(builder: ExecutorBuilder) -> ExecutorBuilder:
        _EXECUTOR_BUILDERS[(interpreter.check_backend(backend), batched, fused)] = builder
        return builder

    return deco


@register_executor("xla", batched=False, fused=False)
def _xla_single(plan: OverlayPlan) -> Callable:
    return partial(interpreter.overlay_step, plan.grid)


@register_executor("xla", batched=False, fused=True)
def _xla_single_fused(plan: OverlayPlan) -> Callable:
    if plan.tile_rows is not None:
        # Single-app tiled execution rides the batched tiled twin with N=1
        # (mirrors the pallas single-app adapters in kernels/vcgra/ops.py).
        batched = partial(
            interpreter.tiled_batched_fused_overlay_step,
            plan.grid, plan.radius, plan.tile_rows,
        )

        def fn(config, ingest, image):
            lift = partial(jax.tree_util.tree_map, lambda a: a[None])
            return batched(lift(config), lift(ingest), image[None])[0]

        return fn
    return partial(interpreter.fused_overlay_step, plan.grid, plan.radius)


@register_executor("xla", batched=True, fused=False)
def _xla_batched(plan: OverlayPlan) -> Callable:
    return partial(interpreter.batched_overlay_step, plan.grid)


@register_executor("xla", batched=True, fused=True)
def _xla_batched_fused(plan: OverlayPlan) -> Callable:
    if plan.tile_rows is not None:
        step = partial(
            interpreter.tiled_batched_fused_overlay_step,
            plan.grid, plan.radius, plan.tile_rows,
        )
    else:
        step = partial(interpreter.batched_fused_overlay_step, plan.grid,
                       plan.radius)

    def fn(configs, ingests, hw, images):
        return step(configs, ingests, images, hw)

    return fn


# -- pipeline executors --------------------------------------------------------


class _BankChannels:
    """Duck-typed ``[C, pixels]`` channel input for
    :func:`repro.core.specialize.build_specialized_fn`: channels are
    produced lazily from ONE app's tap bank by the stage's *static*
    ingest plan, so only channels the specialized trace actually fetches
    are ever formed -- dead taps cost nothing, exactly like the dead
    functional units the specializer already folds away."""

    def __init__(self, bank: jnp.ndarray, ingest, dtype):
        self._bank = bank            # [T+1, pixels]
        self._ingest = ingest
        self.shape = (int(ingest.tap_sel.shape[0]),) + bank.shape[1:]
        self.dtype = dtype

    def __getitem__(self, c: int) -> jnp.ndarray:
        t = int(self._ingest.tap_sel[c])
        if t == self._ingest.zero_row:
            # Const (or zero-pad) channel: a scalar; apply_op broadcasting
            # and the specializer's final broadcast_to widen it.
            return jnp.asarray(self._ingest.const_vals[c], self.dtype)
        return self._bank[t]


def _pipeline_specialized_fn(plan: "OverlayPlan") -> Callable:
    """Single-device XLA pipeline executor, specialized at trace time.

    The plan's :class:`PipelineSpec`s are static, so each (app, stage)
    pair traces through ``specialize.build_specialized_fn``: only the
    configured functional unit per PE is emitted (no all-units-plus-mux
    generic datapath) and every VC select folds to direct SSA wiring --
    the paper's parameterized-vs-conventional distinction, applied per
    stage of the chain.  This is where the pipeline bench's speedup over
    the staged generic dispatches comes from; the inter-stage hop is just
    a reshape + mask, never a host transfer.

    Bitwise equal to the generic path: per live PE both compute the same
    ``apply_op`` formula on the same operands, and channel production
    selects the same bank rows / consts.
    """
    from repro.core.specialize import build_specialized_fn

    grid = plan.grid
    specs = plan.pipeline
    radii = specs[0].radii
    depth = len(radii)
    stage_fns = [
        [build_specialized_fn(grid, spec.stages[si].config) for spec in specs]
        for si in range(depth)
    ]

    def fn(stage_settings, hw, images):
        del stage_settings  # identity lives in the plan (trace-time consts)
        x = jnp.asarray(images, grid.dtype)
        n, H, W = x.shape
        if n != len(specs):
            raise ValueError(
                f"pipeline plan carries {len(specs)} app slots, dispatch "
                f"has {n} frames"
            )
        valid = interpreter.valid_pixel_mask(hw, H, W)
        ys = None
        for si in range(depth):
            bank = interpreter.form_tap_bank(x, radii[si], grid.dtype)
            ys = jnp.stack(
                [
                    stage_fns[si][a](
                        _BankChannels(
                            bank[a], specs[a].stages[si].config.ingest,
                            grid.dtype,
                        )
                    )
                    for a in range(n)
                ],
                axis=0,
            )
            if si < depth - 1:
                # out_channel is static per app slot: a plain view, no
                # gather.
                y = jnp.stack(
                    [ys[a, specs[a].stages[si].out_channel] for a in range(n)],
                    axis=0,
                )
                x = jnp.where(valid, y.reshape(n, H, W), 0)
        return interpreter.mask_outputs(ys, hw, H, W)

    return fn


def _pipeline_stage_fn(plan: "OverlayPlan") -> Callable:
    """Per-stage executor ``stage_fn(radius, configs, ingests, hw, x)`` for the
    operand-settings pipeline chain (mesh-sharded paths: SPMD traces once,
    so per-shard trace-time constants are impossible and settings stay
    runtime data, exactly like single-stage sharded dispatch)."""
    if plan.backend == "pallas":
        from repro.kernels.vcgra.ops import pallas_pipeline_stage_fn

        return pallas_pipeline_stage_fn(plan.grid, plan.tile_rows)
    if plan.tile_rows is not None:
        def stage(radius, configs, ingests, hw, x):
            return interpreter.tiled_batched_fused_overlay_step(
                plan.grid, radius, plan.tile_rows, configs, ingests, x, hw
            )

        return stage

    def stage(radius, configs, ingests, hw, x):
        return interpreter.batched_fused_overlay_step(
            plan.grid, radius, configs, ingests, x, hw
        )

    return stage


def _with_pipeline_mesh_padding(fn: Callable, spec: MeshSpec,
                                radius: int) -> Callable:
    """:func:`_with_mesh_padding` for the pipeline signature
    ``(stage_settings, hw, images)``: pad the app axis of every settings
    leaf (replaying the last slot) and the frame rows to ``row_band(H,
    rows, max_radius) * rows`` zeros, slice both back off.  ``hw`` keeps
    the true per-app sizes, so the in-chain mask also zeroes the pad rows
    between stages -- which is what makes replay-padding exact for chains
    (the padded slots' garbage never crosses a halo exchange)."""
    app, rows = spec.app, spec.rows

    def padded(stage_settings, hw, images):
        n, H, W = images.shape
        pad_n = (-n) % app
        if pad_n:
            stage_settings, hw, images = jax.tree_util.tree_map(
                lambda a: jnp.concatenate(
                    [a, jnp.broadcast_to(a[-1:], (pad_n,) + a.shape[1:])],
                    axis=0,
                ),
                (stage_settings, hw, images),
            )
        band = row_band(H, rows, radius)
        pad_h = band * rows - H
        if pad_h:
            images = jnp.pad(images, ((0, 0), (0, pad_h), (0, 0)))
        ys = fn(stage_settings, hw, images)
        if pad_h:
            ys = ys.reshape(ys.shape[0], ys.shape[1], band * rows, W)
            ys = ys[:, :, :H, :].reshape(ys.shape[0], ys.shape[1], H * W)
        return ys[:n] if pad_n else ys

    return padded


def _compile_pipeline(plan: "OverlayPlan") -> "OverlayExecutable":
    """Compile a depth>1 pipeline plan into ONE executable
    ``fn(stage_settings, hw, images)`` whose intermediates never leave the
    device.

    Single-device XLA: the trace-time-specialized chain
    (:func:`_pipeline_specialized_fn`).  Single-device Pallas: the
    multi-stage megakernel (stage loop over the same VMEM scratch slabs,
    total pad = sum of stage radii).  Mesh-sharded (either backend): the
    operand-settings chain, app-sharded via ``shard_apps`` or row-banded
    with per-stage halo exchange via ``shard_pipeline_rows``.  All paths
    are bitwise equal to the staged per-stage oracle.
    """
    radii = plan.pipeline[0].radii
    mesh = build_mesh(plan.mesh) if plan.mesh.size > 1 else None
    if mesh is None:
        if plan.backend == "pallas":
            from repro.kernels.vcgra.ops import pallas_pipeline_fn

            fn = pallas_pipeline_fn(plan.grid, radii, plan.tile_rows)
        else:
            fn = _pipeline_specialized_fn(plan)
    else:
        stage_fn = _pipeline_stage_fn(plan)
        if plan.mesh.rows > 1:
            fn = _with_pipeline_mesh_padding(
                shard_pipeline_rows(stage_fn, mesh, radii),
                plan.mesh, plan.radius,
            )
        else:
            chain = partial(
                interpreter.pipeline_batched_fused_step,
                plan.grid, radii, stage_fn,
            )
            fn = _with_app_padding(shard_apps(chain, mesh, 3), plan.mesh.app)
    donate = ()
    if plan.ingest == "async" and jax.default_backend() != "cpu":
        donate = (2,)
        _install_donation_warning_filter()
    return OverlayExecutable(
        plan, _jit_named(fn, "pixie_pipeline_dispatch", donate), mesh=mesh)


def _jit_named(fn: Callable, name: str, donate: Tuple[int, ...]) -> Callable:
    """``jax.jit(fn)`` under a stable ``name``: the jitted module is
    ``jit_<name>`` in the compiled text and the device trace, whatever the
    executor's inner function is called, so a trace tells the fused,
    pipeline and packed executables apart."""

    def named(*args):
        return fn(*args)

    named.__name__ = named.__qualname__ = name
    return jax.jit(named, donate_argnums=donate)


# -- the compile pipeline ------------------------------------------------------


def _with_app_padding(fn: Callable, devices: int) -> Callable:
    """Pad the app axis of every operand to a multiple of the mesh size
    (replaying the last app -- always a valid config on valid inputs, so
    no NaN/garbage risk) and slice the output back.  Shapes are static
    under jit, so the pad amount is a trace-time constant and the padded
    executable is still compile-once per operand shape."""

    def padded(*args):
        n = jax.tree_util.tree_leaves(args[-1])[0].shape[0]
        pad = (-n) % devices
        if not pad:
            return fn(*args)
        args = jax.tree_util.tree_map(
            lambda a: jnp.concatenate(
                [a, jnp.broadcast_to(a[-1:], (pad,) + a.shape[1:])], axis=0
            ),
            args,
        )
        return fn(*args)[:n]

    return padded


def _with_mesh_padding(fn: Callable, spec: MeshSpec, radius: int) -> Callable:
    """The 2-D twin of :func:`_with_app_padding` for row-banded fused
    dispatch: pad the app axis to a multiple of ``spec.app`` (replaying
    the last app) AND the frame's row axis to ``row_band(H, rows, radius)
    * rows`` zero rows, then slice both back off the output.

    The row floor at ``radius`` guarantees every shard's band is at least
    as deep as the stencil reach, so the single-hop seam exchange of
    ``halo_exchange_rows`` is always sufficient.  Zero pad rows are read
    only as bottom-border zeros -- exactly ``form_tap_bank``'s border --
    and their outputs are discarded, so padding is bitwise exact.  Shapes
    are static under jit: both pad amounts are trace-time constants."""
    app, rows = spec.app, spec.rows

    def padded(configs, ingests, hw, images):
        n, H, W = images.shape
        pad_n = (-n) % app
        if pad_n:
            configs, ingests, hw, images = jax.tree_util.tree_map(
                lambda a: jnp.concatenate(
                    [a, jnp.broadcast_to(a[-1:], (pad_n,) + a.shape[1:])],
                    axis=0,
                ),
                (configs, ingests, hw, images),
            )
        band = row_band(H, rows, radius)
        pad_h = band * rows - H
        if pad_h:
            images = jnp.pad(images, ((0, 0), (0, pad_h), (0, 0)))
        ys = fn(configs, ingests, hw, images)
        if pad_h:
            ys = ys.reshape(ys.shape[0], ys.shape[1], band * rows, W)
            ys = ys[:, :, :H, :].reshape(ys.shape[0], ys.shape[1], H * W)
        return ys[:n] if pad_n else ys

    return padded


def compile_plan(plan: OverlayPlan) -> OverlayExecutable:
    """THE overlay compile entrypoint: plan -> jitted executable.

    Subsumes the former ``make_overlay_fn`` / ``make_batched_overlay_fn``
    / ``make_fused_overlay_fn`` / ``make_batched_fused_overlay_fn`` x
    backend matrix (those survive as deprecated shims delegating here).
    Builds the backend's executor, wraps it in ``shard_map`` over the
    plan's mesh when ``plan.mesh`` asks for more than one device and the
    host can grant it (single-device bitwise fallback otherwise -- 1-D
    app sharding via ``shard_apps``, 2-D app x rows sharding with seam
    halo exchange via ``shard_apps_rows``), and jits exactly once.
    """
    if plan.pipeline is not None:
        return _compile_pipeline(plan)
    if plan.backend == "pallas":
        # Importing the kernel package registers its plan executors.
        import repro.kernels.vcgra.ops  # noqa: F401

    builder = _EXECUTOR_BUILDERS.get((plan.backend, plan.batched, plan.fused))
    if builder is None:  # pragma: no cover - registry covers the full matrix
        raise ValueError(f"no executor registered for plan {plan.key()}")
    fn = builder(plan)

    num_args = (4 if plan.batched else 3) if plan.fused else 2
    mesh = None
    if plan.mesh.size > 1:
        mesh = build_mesh(plan.mesh)
        if mesh is not None and plan.mesh.rows > 1:
            fn = _with_mesh_padding(
                shard_apps_rows(fn, mesh, plan.radius), plan.mesh, plan.radius
            )
        elif mesh is not None:
            fn = _with_app_padding(
                shard_apps(fn, mesh, num_args), plan.mesh.app
            )
    # Async-ingest plans donate the trailing operand (the frames canvas /
    # channel stack): the double-buffered pipeline ships a fresh
    # device_put buffer per dispatch, so XLA may reuse its memory for the
    # outputs instead of holding both live.  The settings/ingest banks are
    # cross-flush caches and are never donated.  Accelerators only: on
    # XLA:CPU donation buys nothing (host memory is not the scarce
    # resource) and measurably slows the fused executable (~4% at 256^2
    # -- input aliasing constrains its buffer assignment), so the CPU
    # async path keeps the donation-free executable.
    donate = ()
    if plan.ingest == "async" and jax.default_backend() != "cpu":
        donate = (num_args - 1,)
        _install_donation_warning_filter()
    name = "pixie_fused_dispatch" if plan.fused else "pixie_packed_dispatch"
    return OverlayExecutable(plan, _jit_named(fn, name, donate), mesh=mesh)


_DONATION_FILTER_INSTALLED = False


def _install_donation_warning_filter() -> None:
    """Donation is a best-effort memory hint, not a contract: backends
    that cannot alias the operand into an output warn on first lowering.
    Filter just that message, once, and only when donation is actually in
    play -- importing this module must not mute the diagnostic for
    unrelated user code, and repeat compiles must not pile duplicate
    entries onto the process-global filter list."""
    global _DONATION_FILTER_INSTALLED
    if not _DONATION_FILTER_INSTALLED:
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        _DONATION_FILTER_INSTALLED = True
