"""The unified OverlayPlan compile/dispatch pipeline.

Plan identity IS the cache key: the same app stack on xla vs pallas, or
1-device vs mesh-sharded, must produce distinct ``OverlayPlan`` keys and
hit the fleet's LRU independently.  The deprecated ``make_*_overlay_fn``
shims must stay bitwise-equal to ``compile_plan`` while warning.  The
sharded tests (active when >= 2 local devices are present -- CI's
sharded-parity job forces two with
``XLA_FLAGS=--xla_force_host_platform_device_count=2``) assert that
app-axis ``shard_map`` dispatch is bitwise identical to the
single-device run on ragged, non-square app stacks for both backends.
"""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    OverlayExecutable, OverlayPlan, Pixie, compile_plan, map_app, sobel_grid,
)
from repro.core import applications as apps
from repro.core import interpreter
from repro.core.bitstream import VCGRAConfig
from repro.core.ingest import IngestPlan
from repro.core.tiling import pad_batches, pad_channels, pow2_bucket, round_up
from repro.parallel.axes import app_mesh
from repro.runtime.fleet import FleetRequest, PixieFleet

GRID = sobel_grid()
MULTI_DEVICE = len(jax.local_devices()) >= 2
needs_two_devices = pytest.mark.skipif(
    not MULTI_DEVICE,
    reason="needs >= 2 local devices (CI sharded-parity job forces 2 via "
    "XLA_FLAGS=--xla_force_host_platform_device_count=2)",
)


def _stacked_workload(rng, names, hws):
    """Ragged non-square frames embedded on one canvas + stacked settings."""
    images = [rng.integers(0, 256, hw).astype(np.int32) for hw in hws]
    configs = [map_app(apps.ALL_APPS[n](), GRID) for n in names]
    Hb, Wb = max(h for h, _ in hws), max(w for _, w in hws)
    canvas = np.zeros((len(names), Hb, Wb), dtype=np.int32)
    for i, img in enumerate(images):
        canvas[i, : img.shape[0], : img.shape[1]] = img
    return (
        VCGRAConfig.stack(configs),
        IngestPlan.stack([c.ingest for c in configs], GRID.dtype),
        jnp.asarray(canvas),
    )


# -- plan identity ------------------------------------------------------------


def test_plan_axes_produce_distinct_hashable_keys():
    """Every axis of the matrix distinguishes the plan; plans are usable
    as dict/LRU keys directly."""
    base = OverlayPlan(grid=GRID, batched=True, fused=True)
    variants = [
        base,
        OverlayPlan(grid=GRID, batched=True, fused=True, backend="pallas"),
        OverlayPlan(grid=GRID, batched=True, fused=True, devices=2),
        OverlayPlan(grid=GRID, batched=True, fused=True, radius=2),
        OverlayPlan(grid=GRID, batched=True, fused=False),
        OverlayPlan(grid=GRID, batched=False, fused=True),
    ]
    assert len({hash(p) for p in variants}) == len(variants)
    assert len({p.key() for p in variants}) == len(variants)
    assert len(dict.fromkeys(variants)) == len(variants)
    # equal plans are one key
    assert OverlayPlan(grid=GRID, batched=True, fused=True) == base
    assert hash(OverlayPlan(grid=GRID, batched=True, fused=True)) == hash(base)


def test_plan_validation_and_canonicalization():
    with pytest.raises(ValueError, match="unknown backend"):
        OverlayPlan(grid=GRID, backend="cuda")
    with pytest.raises(ValueError, match="devices"):
        OverlayPlan(grid=GRID, batched=True, devices=0)
    with pytest.raises(ValueError, match="batched"):
        OverlayPlan(grid=GRID, batched=False, devices=2)
    with pytest.raises(ValueError, match="radius"):
        OverlayPlan(grid=GRID, fused=False, radius=1)
    with pytest.raises(ValueError, match="radius"):
        OverlayPlan(grid=GRID, fused=True, radius=-1)
    # radius 0 is a VALID fused plan since PR 9: a depth-1 pointwise
    # pipeline stage (threshold at radius 0) canonicalizes onto it
    assert OverlayPlan(grid=GRID, fused=True, radius=0).radius == 0
    # fused plans canonicalize a missing radius to 1 (one key per bank)
    assert OverlayPlan(grid=GRID, fused=True).radius == 1
    assert OverlayPlan(grid=GRID, fused=True) == OverlayPlan(
        grid=GRID, fused=True, radius=1
    )


def test_compile_plan_returns_executable_with_plan():
    plan = OverlayPlan(grid=GRID, batched=True, fused=True)
    exe = compile_plan(plan)
    assert isinstance(exe, OverlayExecutable)
    assert exe.plan == plan and exe.mesh is None
    assert GRID.name in repr(exe)


# -- fleet LRU keyed on plans -------------------------------------------------


def test_fleet_lru_hits_independently_per_plan(rng):
    """Same app stack on xla vs pallas fleets: distinct plan keys, each
    LRU built exactly once and hit on the repeat flush."""
    img = rng.integers(0, 256, (7, 7)).astype(np.int32)
    reqs = [FleetRequest(app=n, image=img) for n in ("sobel_x", "identity")]
    fleets = {b: PixieFleet(default_grid=GRID, backend=b)
              for b in ("xla", "pallas")}
    plans = {}
    for b, fleet in fleets.items():
        fleet.run_many(reqs)
        fleet.run_many(reqs)
        assert fleet.stats.overlay_builds == 1
        assert fleet.stats.overlay_cache_hits == 1
        (plan,) = fleet._overlays._d.keys()
        plans[b] = plan
        assert plan.backend == b and plan.fused and plan.batched
    assert plans["xla"] != plans["pallas"]
    # 1-device vs sharded is a distinct key too (even off-mesh)
    sharded = PixieFleet(default_grid=GRID, devices=2)
    assert sharded.plan_for_dispatch(GRID, fused=True, radius=1) != plans["xla"]
    assert len({plans["xla"], plans["pallas"],
                sharded.plan_for_dispatch(GRID, fused=True, radius=1)}) == 3


def test_fleet_stats_stamp_full_plan_key(rng):
    img = rng.integers(0, 256, (5, 5)).astype(np.int32)
    fleet = PixieFleet(default_grid=GRID, backend="xla")
    fleet.run_many([FleetRequest(app="sobel_x", image=img)])
    assert fleet.stats.devices == 1
    (key,) = fleet.stats.dispatch_plans
    # the stamp names grid, fusion+radius, backend, devices and the tile
    for part in (GRID.name, "fused:r1", "xla", "dev1", "n8x"):
        assert part in key, (part, key)
    assert fleet.stats.as_dict()["dispatch_plans"][key] == 1


# -- deprecated shims ---------------------------------------------------------


def _sobel_operands(rng):
    img = rng.integers(0, 256, (8, 11)).astype(np.int32)
    cfg = map_app(apps.sobel_x(), GRID)
    taps = apps.stencil_inputs(jnp.asarray(img))
    feed = {k: v for k, v in taps.items() if k in cfg.input_order}
    x = pad_channels(interpreter.pack_inputs(cfg, feed, GRID.dtype),
                     GRID.num_inputs)
    return img, cfg, x


def test_deprecated_shims_warn_and_match_compile_plan_bitwise(rng):
    img, cfg, x = _sobel_operands(rng)
    stacked = VCGRAConfig.stack([cfg, cfg])
    ingests = IngestPlan.stack([cfg.ingest, cfg.ingest], GRID.dtype)
    xs = jnp.stack([x, x])
    imgs = jnp.stack([jnp.asarray(img)] * 2)

    cases = [
        (lambda: interpreter.make_overlay_fn(GRID),
         OverlayPlan(grid=GRID), (cfg.to_jax(), x)),
        (lambda: interpreter.make_batched_overlay_fn(GRID),
         OverlayPlan(grid=GRID, batched=True), (stacked, xs)),
        (lambda: interpreter.make_fused_overlay_fn(GRID),
         OverlayPlan(grid=GRID, fused=True),
         (cfg.to_jax(), cfg.ingest.to_jax(GRID.dtype), jnp.asarray(img))),
        (lambda: interpreter.make_batched_fused_overlay_fn(GRID),
         OverlayPlan(grid=GRID, batched=True, fused=True),
         (stacked, ingests, None, imgs)),
    ]
    for make, plan, operands in cases:
        with pytest.warns(DeprecationWarning, match="compile_plan"):
            shim = make()
        assert isinstance(shim, OverlayExecutable) and shim.plan == plan
        np.testing.assert_array_equal(
            np.asarray(shim(*operands)),
            np.asarray(compile_plan(plan)(*operands)),
        )


def test_deprecated_shims_still_reject_unknown_backend():
    with pytest.raises(ValueError, match="unknown backend"):
        interpreter.make_batched_fused_overlay_fn(GRID, backend="cuda")


# -- single-app backend threading (Pixie facade) ------------------------------


def test_pixie_facade_backend_pallas_bitwise(rng):
    """Single-app users exercise the pallas path without a fleet: the
    facade's plans carry backend= and stay bitwise-equal to xla."""
    img = rng.integers(0, 256, (9, 6)).astype(np.int32)
    cfg = map_app(apps.sobel_x(), GRID)
    outs = {}
    for backend in ("xla", "pallas"):
        pix = Pixie(GRID, backend=backend)
        pix.load(cfg)
        outs[backend] = np.asarray(pix.run_image(jnp.asarray(img)))
    np.testing.assert_array_equal(outs["xla"], outs["pallas"])
    np.testing.assert_array_equal(
        outs["xla"], apps.conv2d_reference(img, apps.SOBEL_X)
    )


def test_pixie_run_many_backend_pallas_bitwise(rng):
    img = rng.integers(0, 256, (6, 10)).astype(np.int32)
    taps = apps.stencil_inputs(jnp.asarray(img))
    reqs = []
    for n in ("sobel_x", "laplace"):
        dfg = apps.ALL_APPS[n]()
        reqs.append((dfg, {k: v for k, v in taps.items() if k in dfg.inputs}))
    ref = Pixie(GRID).run_many(reqs)
    got = Pixie(GRID, backend="pallas").run_many(reqs)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_pixie_parameterized_rejects_plan_axes():
    with pytest.raises(ValueError, match="conventional"):
        Pixie(GRID, mode="parameterized", backend="pallas")
    with pytest.raises(ValueError, match="conventional"):
        Pixie(GRID, mode="parameterized", devices=2)
    # devices=1 is the documented no-mesh default, identical to omitting it
    assert Pixie(GRID, mode="parameterized", devices=1).devices == 1


def test_devices_zero_rejected_everywhere():
    """devices=0 must raise like every sibling API, not silently coerce
    to the single-device default."""
    for bad in (0, -1):
        with pytest.raises(ValueError, match="devices"):
            PixieFleet(devices=bad)
        with pytest.raises(ValueError, match="devices"):
            Pixie(GRID, devices=bad)
        with pytest.raises(ValueError, match="devices"):
            OverlayPlan(grid=GRID, batched=True, devices=bad)


# -- tiling single source of truth --------------------------------------------


def test_tiling_helpers_single_source():
    assert round_up(5, 4) == 8 and round_up(8, 4) == 8
    assert pow2_bucket(17, 16) == 32 and pow2_bucket(3, 16) == 16
    # the interpreter re-exports the same objects, not copies
    assert interpreter.pad_channels is pad_channels
    assert interpreter.pad_batches is pad_batches


# -- mesh-sharded dispatch ----------------------------------------------------


def test_app_mesh_single_device_fallback():
    assert app_mesh(1) is None
    assert app_mesh(10_000) is None  # more than any host: fall back, not raise
    exe = compile_plan(OverlayPlan(grid=GRID, batched=True, fused=True,
                                   devices=10_000))
    assert exe.mesh is None  # single-device bitwise fallback


@needs_two_devices
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_sharded_compile_plan_bitwise_ragged_nonsquare(backend, rng):
    """devices=2 batched fused dispatch == single-device, bitwise, on a
    ragged non-square app stack -- including N=5 (not divisible by the
    mesh, exercising the internal app-axis padding)."""
    names = ["sobel_x", "sobel_y", "sharpen", "laplace", "identity"]
    hws = [(5, 9), (12, 4), (7, 7), (3, 11), (10, 6)]
    stacked, ingests, canvas = _stacked_workload(rng, names, hws)
    one = compile_plan(OverlayPlan(grid=GRID, batched=True, fused=True,
                                   backend=backend))
    two = compile_plan(OverlayPlan(grid=GRID, batched=True, fused=True,
                                   backend=backend, devices=2))
    assert two.mesh is not None and two.mesh.shape["app"] == 2
    hw = np.asarray(hws, np.int32)
    np.testing.assert_array_equal(
        np.asarray(one(stacked, ingests, hw, canvas)),
        np.asarray(two(stacked, ingests, hw, canvas)),
    )


@needs_two_devices
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_sharded_fleet_bitwise_ragged(backend, rng):
    """PixieFleet(devices=2) == PixieFleet() on ragged non-square frames,
    both backends; the sharded fleet stamps dev2 plan keys."""
    names = ["sobel_x", "sharpen", "identity"]
    images = [rng.integers(0, 256, hw).astype(np.int32)
              for hw in [(6, 8), (11, 5), (3, 9)]]
    reqs = [FleetRequest(app=n, image=i) for n, i in zip(names, images)]
    ref = PixieFleet(default_grid=GRID, backend=backend).run_many(reqs)
    fleet = PixieFleet(default_grid=GRID, backend=backend, devices=2)
    got = fleet.run_many(reqs)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(a, b)
    assert fleet.stats.devices == 2
    assert all("dev2" in k for k in fleet.stats.dispatch_plans)


@needs_two_devices
def test_sharded_mixed_fused_and_channel_requests(rng):
    """A sharded flush mixing fused frames and named channels: both
    dispatch paths shard and stay bitwise-exact."""
    img = rng.integers(0, 256, (6, 9)).astype(np.int32)
    x = rng.integers(0, 256, (23,)).astype(np.int32)
    reqs = [
        FleetRequest(app="sobel_x", image=img),
        FleetRequest(app="threshold", inputs={"p11": x}),
    ]
    ref = PixieFleet(default_grid=GRID).run_many(reqs)
    got = PixieFleet(default_grid=GRID, devices=2).run_many(reqs)
    np.testing.assert_array_equal(ref[0], got[0])
    np.testing.assert_array_equal(ref[1], got[1])


@needs_two_devices
def test_sharded_pixie_run_many_bitwise(rng):
    img = rng.integers(0, 256, (6, 10)).astype(np.int32)
    taps = apps.stencil_inputs(jnp.asarray(img))
    reqs = []
    for n in ("sobel_x", "laplace", "identity"):
        dfg = apps.ALL_APPS[n]()
        reqs.append((dfg, {k: v for k, v in taps.items() if k in dfg.inputs}))
    ref = Pixie(GRID).run_many(reqs)  # N=3: internal pad to the mesh
    got = Pixie(GRID, devices=2).run_many(reqs)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# -- front-end plumbing -------------------------------------------------------


def test_frontend_devices_kwarg_and_conflict():
    from repro.serve.fleet_frontend import FleetFrontend

    svc = FleetFrontend(devices=1)
    assert svc.devices == 1
    with pytest.raises(ValueError, match="conflicts"):
        FleetFrontend(fleet=PixieFleet(devices=1), devices=2)


def test_no_spurious_deprecation_warnings_on_plan_paths(rng):
    """The rewired production paths (fleet, facade) must not route through
    the deprecated shims."""
    img = rng.integers(0, 256, (5, 5)).astype(np.int32)
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        PixieFleet(default_grid=GRID).run_many(
            [FleetRequest(app="sobel_x", image=img)]
        )
        pix = Pixie(GRID)
        pix.load(map_app(apps.sobel_x(), GRID))
        pix.run_image(jnp.asarray(img))
