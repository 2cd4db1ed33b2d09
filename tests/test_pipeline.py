"""Device-resident pipeline plans: a chained overlay (stage i's selected
output channel feeds stage i+1's ingest taps) compiles to ONE
`OverlayExecutable` whose intermediates never leave the device.  Every
fused chain here is asserted BITWISE equal to the staged per-stage oracle
(one single-stage run per stage with a host hop between), on both
backends, through every layer: the plan/key algebra, the compiled
executors, the fleet (sync + async ingest, mixed flushes, depth-1
demotion), `Pixie.run_pipeline`, both serving front-ends, and the
row-sharded mesh path (device-gated)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import shared_app_grid

from repro.core import MeshSpec, OverlayPlan, Pixie, compile_plan, map_app
from repro.core import applications as apps
from repro.core.bitstream import VCGRAConfig
from repro.core.ingest import IngestPlan
from repro.core.plan import PipelineSpec, PipelineStage, pipeline_digest
from repro.runtime.fleet import FleetRequest, PixieFleet
from repro.serve import FleetFrontend, StreamingFrontend

N_DEVICES = len(jax.local_devices())
needs_two_devices = pytest.mark.skipif(
    N_DEVICES < 2,
    reason="needs >= 2 local devices (CI pipeline-parity job forces 2 via "
    "XLA_FLAGS=--xla_force_host_platform_device_count=2)",
)

# The canonical depth-3 chain: blur -> edge -> binarize (radii 1/1/1; the
# threshold stage is pointwise and re-plans to radius 0 in the mixed-radii
# tests).  One shared grid fits every stage (Sec. III-C's "application
# specific grid designs" at the union of demands).
CHAIN = ["gauss3", "sobel_x", "threshold"]
GRID = shared_app_grid(CHAIN, name="pipe-shared")
WAIT = 30.0


def chain_configs(grid=GRID, names=CHAIN):
    return [map_app(apps.ALL_APPS[n](), grid) for n in names]


def staged_oracle(cfgs, image, grid=GRID, out_channels=None):
    """Per-stage host-hop reference: stage i runs alone, its [H, W]
    output (selected channel) is re-submitted as stage i+1's frame."""
    chans = list(out_channels) if out_channels else [0] * len(cfgs)
    pix = Pixie(grid, mode="conventional")
    cur = np.asarray(image)
    for cfg, ch in zip(cfgs, chans):
        pix.load(cfg)
        y = np.asarray(pix.run_image(jnp.asarray(cur)))
        cur = y if y.ndim == 2 else y[ch]
    return cur


# -- spec construction + validation -------------------------------------------


def test_stage_requires_ingest_plan():
    cfg = chain_configs()[0]
    bare = dataclasses.replace(cfg, ingest=None)
    with pytest.raises(ValueError, match="no ingest"):
        PipelineStage(bare)


def test_stage_out_channel_range():
    cfg = chain_configs()[0]
    with pytest.raises(ValueError, match="out_channel"):
        PipelineStage(cfg, out_channel=len(cfg.out_sel))


def test_spec_needs_at_least_one_stage():
    with pytest.raises(ValueError, match="at least one stage"):
        PipelineSpec(())


def test_spec_rejects_mixed_grids():
    other = shared_app_grid(CHAIN, name="pipe-other")
    a = map_app(apps.ALL_APPS["gauss3"](), GRID)
    b = map_app(apps.ALL_APPS["sobel_x"](), other)
    with pytest.raises(ValueError, match="ONE overlay grid"):
        PipelineSpec((PipelineStage(a), PipelineStage(b)))


def test_at_radius_replans_pointwise_stage():
    thr = map_app(apps.ALL_APPS["threshold"](), GRID)
    thr.cache_key = "thr@pipe-shared"  # as the fleet's config_for would set
    stage = PipelineStage(thr)
    assert stage.radius == 1
    r0 = stage.at_radius(0)
    assert r0.radius == 0 and r0 != stage
    # the radius-keyed settings banks must never alias the original
    assert r0.config.cache_key == "thr@pipe-shared@r0"
    assert stage.at_radius(1) is stage


def test_spec_digest_is_content_addressed():
    cfgs = chain_configs()
    assert PipelineSpec.chain(cfgs) == PipelineSpec.chain(chain_configs())
    assert hash(PipelineSpec.chain(cfgs)) == hash(PipelineSpec.chain(cfgs))
    assert PipelineSpec.chain(cfgs) != PipelineSpec.chain(cfgs[:2])
    spec = PipelineSpec.chain(cfgs)
    assert spec.depth == 3 and spec.radii == (1, 1, 1)
    assert spec.total_radius == 3


# -- plan algebra: canonicalization + key compatibility -----------------------


def test_depth1_pipeline_canonicalizes_to_plain_fused_plan():
    """A single-stage "chain" IS the existing batched fused plan: same
    key, same hash, same cache entry -- every pre-pipeline executable
    population survives the new axis."""
    cfg = chain_configs()[:1]
    spec = PipelineSpec.chain(cfg)
    p_pipe = OverlayPlan(grid=GRID, batched=True, pipeline=(spec, spec))
    p_plain = OverlayPlan(grid=GRID, batched=True, fused=True, radius=1)
    assert p_pipe.pipeline is None
    assert p_pipe.radius == 1 and p_pipe.fused
    assert p_pipe.key() == p_plain.key()
    assert p_pipe == p_plain and hash(p_pipe) == hash(p_plain)


def test_deep_pipeline_key_appends_pipe_segment_only():
    spec = PipelineSpec.chain(chain_configs())
    p = OverlayPlan(grid=GRID, batched=True, pipeline=(spec,))
    plain = OverlayPlan(grid=GRID, batched=True, fused=True, radius=1)
    assert "|pipe" in p.key() and "|pipe" not in plain.key()
    assert p.key() == plain.key() + f"|pipe{pipeline_digest(p.pipeline)[:12]}"
    # identity: same chain -> same plan; different chain -> different key
    p2 = OverlayPlan(grid=GRID, batched=True, pipeline=(spec,))
    assert p == p2 and p.key() == p2.key()
    p3 = OverlayPlan(
        grid=GRID, batched=True,
        pipeline=(PipelineSpec.chain(chain_configs()[:2]),),
    )
    assert p3.key() != p.key()


def test_pipeline_plan_validation():
    spec = PipelineSpec.chain(chain_configs())
    with pytest.raises(ValueError, match="batched"):
        OverlayPlan(grid=GRID, pipeline=(spec,))
    with pytest.raises(ValueError, match="radius is derived"):
        OverlayPlan(grid=GRID, batched=True, radius=1, pipeline=(spec,))
    other = shared_app_grid(CHAIN, name="pipe-other2")
    with pytest.raises(ValueError, match="cannot run on plan grid"):
        OverlayPlan(grid=other, batched=True, pipeline=(spec,))
    short = PipelineSpec.chain(chain_configs()[:2])
    with pytest.raises(ValueError, match="stage structure"):
        OverlayPlan(grid=GRID, batched=True, pipeline=(spec, short))
    # plan radius of a chain = max stage radius (rows-band floor)
    p = OverlayPlan(grid=GRID, batched=True, pipeline=(spec,))
    assert p.radius == 1 and p.fused


# -- compiled executors: fused chain == staged oracle, both backends ----------


def _stage_settings(specs):
    return tuple(
        (
            VCGRAConfig.stack([s.stages[si].config for s in specs]),
            IngestPlan.stack(
                [s.stages[si].config.ingest for s in specs], GRID.dtype
            ),
            jnp.asarray([s.stages[si].out_channel for s in specs], jnp.int32),
        )
        for si in range(specs[0].depth)
    )


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_executor_parity_ragged_stack(backend, rng):
    """Depth-3 chain over a ragged 3-frame stack: the single executable's
    per-app crops match the per-stage oracle bitwise.  Raggedness is the
    hard case -- the executor must re-mask each intermediate to the app's
    true [h, w] region or zero-canvas taps poison the next stage."""
    cfgs = chain_configs()
    spec = PipelineSpec.chain(cfgs)
    hws = [(24, 16), (20, 13), (17, 16)]
    images = [rng.integers(0, 256, hw).astype(np.int32) for hw in hws]
    canvas = np.zeros((3, 24, 16), np.int32)
    for i, im in enumerate(images):
        canvas[i, : im.shape[0], : im.shape[1]] = im

    fn = compile_plan(OverlayPlan(
        grid=GRID, batched=True, pipeline=(spec,) * 3, backend=backend,
    ))
    ys = fn(_stage_settings([spec] * 3),
            jnp.asarray(np.asarray(hws, np.int32)), jnp.asarray(canvas))
    for i, (h, w) in enumerate(hws):
        want = staged_oracle(cfgs, images[i])
        got = np.asarray(ys[i]).reshape(-1, 24, 16)[0, :h, :w]
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_executor_parity_mixed_radii_with_zero(backend, rng):
    """gauss3 (r=1) -> threshold re-planned at r=0: radius-0 stages ride
    the same chain executable (1-tap bank, no column pad)."""
    g = map_app(apps.ALL_APPS["gauss3"](), GRID)
    t = PipelineStage(map_app(apps.ALL_APPS["threshold"](), GRID)).at_radius(0)
    spec = PipelineSpec((PipelineStage(g), t))
    assert spec.radii == (1, 0)
    img = rng.integers(0, 256, (15, 11)).astype(np.int32)

    fn = compile_plan(OverlayPlan(
        grid=GRID, batched=True, pipeline=(spec,), backend=backend,
    ))
    ys = fn(_stage_settings([spec]), jnp.asarray([[15, 11]], jnp.int32),
            jnp.asarray(img)[None])
    want = staged_oracle([g, t.config], img)
    np.testing.assert_array_equal(
        np.asarray(ys[0]).reshape(-1, 15, 11)[0], want
    )


@pytest.mark.parametrize("tile_rows", [None, 8, 5, 16])
def test_pallas_chain_tile_rows_bitwise(tile_rows, rng):
    """The megakernel's trapezoid stage loop is tiling-invariant -- ragged
    last tiles (16 does not divide 24) included; 5 is rounded to a whole
    sublane tile (8)."""
    cfgs = chain_configs()
    spec = PipelineSpec.chain(cfgs)
    img = rng.integers(0, 256, (24, 16)).astype(np.int32)
    fn = compile_plan(OverlayPlan(
        grid=GRID, batched=True, pipeline=(spec,), backend="pallas",
        tile_rows=tile_rows,
    ))
    ys = fn(_stage_settings([spec]), jnp.asarray([[24, 16]], jnp.int32),
            jnp.asarray(img)[None])
    want = staged_oracle(cfgs, img)
    np.testing.assert_array_equal(
        np.asarray(ys[0]).reshape(-1, 24, 16)[0], want
    )


# -- fleet: chained requests batch/tile/cache like single-stage ones ----------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_fleet_pipeline_bitwise(backend, rng):
    cfgs = chain_configs()
    images = [rng.integers(0, 256, (13, 17)).astype(np.int32)
              for _ in range(3)]
    fleet = PixieFleet(default_grid=GRID, backend=backend)
    outs = fleet.run_many(
        [FleetRequest(pipeline=CHAIN, image=im) for im in images]
    )
    for im, got in zip(images, outs):
        np.testing.assert_array_equal(np.asarray(got),
                                      staged_oracle(cfgs, im))
    assert fleet.stats.pipeline_dispatches == 1
    assert fleet.stats.dispatches == 1  # the chain is ONE device operation


def test_fleet_depth1_chain_demotes_to_plain_fused(rng):
    """pipeline=["sobel_x"] batches, caches, and stamps EXACTLY like
    app="sobel_x" -- no pipe segment, no new executable."""
    img = rng.integers(0, 256, (9, 9)).astype(np.int32)
    fleet = PixieFleet(default_grid=GRID)
    a = fleet.run_many([FleetRequest(app="sobel_x", image=img)])[0]
    b = fleet.run_many([FleetRequest(pipeline=["sobel_x"], image=img)])[0]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert fleet.stats.pipeline_dispatches == 0
    assert fleet._overlays.misses == 1  # ONE plan serves both spellings
    assert all("|pipe" not in k for k in fleet.stats.dispatch_plans)


def test_fleet_mixed_flush_chains_and_singles(rng):
    """Chains and single-stage requests share a flush: grouped into one
    pipeline dispatch + one fused dispatch, all outputs bitwise."""
    cfgs = chain_configs()
    img = rng.integers(0, 256, (12, 10)).astype(np.int32)
    fleet = PixieFleet(default_grid=GRID)
    t_chain = fleet.submit(FleetRequest(pipeline=CHAIN, image=img))
    t_single = fleet.submit(FleetRequest(app="sobel_x", image=img))
    t_depth1 = fleet.submit(FleetRequest(pipeline=["gauss3"], image=img))
    outs = fleet.flush()
    assert fleet.stats.dispatches == 2
    assert fleet.stats.pipeline_dispatches == 1
    np.testing.assert_array_equal(
        np.asarray(outs[t_chain]), staged_oracle(cfgs, img)
    )
    np.testing.assert_array_equal(
        np.asarray(outs[t_single]), staged_oracle(cfgs[1:2], img)
    )
    np.testing.assert_array_equal(
        np.asarray(outs[t_depth1]), staged_oracle(cfgs[:1], img)
    )


def test_fleet_pipeline_async_ingest_bitwise(rng):
    cfgs = chain_configs()
    images = [rng.integers(0, 256, (11, 9)).astype(np.int32)
              for _ in range(2)]
    fleet = PixieFleet(default_grid=GRID, ingest="async")
    for _ in range(3):  # canvas-pool rotation across flushes
        outs = fleet.run_many(
            [FleetRequest(pipeline=CHAIN, image=im) for im in images]
        )
        for im, got in zip(images, outs):
            np.testing.assert_array_equal(np.asarray(got),
                                          staged_oracle(cfgs, im))


def test_fleet_pipeline_out_channels_and_plan_reuse(rng):
    img = rng.integers(0, 256, (8, 8)).astype(np.int32)
    fleet = PixieFleet(default_grid=GRID)
    fleet.run_many([FleetRequest(pipeline=CHAIN, image=img,
                                 out_channels=[0, 0, 0])])
    fleet.run_many([FleetRequest(pipeline=CHAIN, image=img)])
    # explicit default out_channels are the same spec: one plan compile
    assert fleet._overlays.misses == 1 and fleet._overlays.hits == 1
    assert any("|pipe" in k for k in fleet.stats.dispatch_plans)


def test_fleet_pipeline_submit_validation(rng):
    img = rng.integers(0, 256, (8, 8)).astype(np.int32)
    fleet = PixieFleet(default_grid=GRID)
    with pytest.raises(ValueError, match="not both"):
        fleet.submit(FleetRequest(app="sobel_x", pipeline=CHAIN, image=img))
    with pytest.raises(ValueError, match="app= or pipeline="):
        fleet.submit(FleetRequest(image=img))
    with pytest.raises(ValueError, match="image"):
        fleet.submit(FleetRequest(pipeline=CHAIN,
                                  inputs={"x": np.zeros(4, np.int32)}))
    with pytest.raises(ValueError, match="at least one stage"):
        fleet.submit(FleetRequest(pipeline=[], image=img))


# -- Pixie facade -------------------------------------------------------------


def test_pixie_run_pipeline_bitwise(rng):
    cfgs = chain_configs()
    img = rng.integers(0, 256, (14, 12)).astype(np.int32)
    pix = Pixie(GRID, mode="conventional")
    got = np.asarray(pix.run_pipeline(CHAIN, jnp.asarray(img)))
    np.testing.assert_array_equal(got, staged_oracle(cfgs, img))
    assert "run_pipeline_s" in pix.timings
    # compiled once per distinct chain
    assert len(pix._pipeline_fns) == 1
    pix.run_pipeline(CHAIN, jnp.asarray(img))
    assert len(pix._pipeline_fns) == 1


def test_pixie_run_pipeline_depth1_is_run_image(rng):
    img = rng.integers(0, 256, (9, 7)).astype(np.int32)
    pix = Pixie(GRID, mode="conventional")
    a = np.asarray(pix.run_pipeline(["sobel_x"], jnp.asarray(img)))
    pix.load(map_app(apps.ALL_APPS["sobel_x"](), GRID))
    b = np.asarray(pix.run_image(jnp.asarray(img)))
    np.testing.assert_array_equal(a, b)
    assert not pix._pipeline_fns  # no chain executable was built


def test_pixie_run_pipeline_requires_conventional(rng):
    img = rng.integers(0, 256, (8, 8)).astype(np.int32)
    pix = Pixie(GRID, mode="parameterized")
    with pytest.raises(RuntimeError, match="conventional"):
        pix.run_pipeline(CHAIN, jnp.asarray(img))


# -- serving front-ends -------------------------------------------------------


def test_frontend_chain_submit_bitwise(rng):
    cfgs = chain_configs()
    img = rng.integers(0, 256, (10, 12)).astype(np.int32)
    svc = FleetFrontend(fleet=PixieFleet(default_grid=GRID))
    h = svc.submit(CHAIN, img)
    np.testing.assert_array_equal(
        np.asarray(h.result()), staged_oracle(cfgs, img)
    )
    assert h.job().app == "gauss3+sobel_x+threshold"
    assert svc.stats.pipeline_dispatches == 1


def test_streaming_chain_submit_bitwise(rng):
    cfgs = chain_configs()
    img = rng.integers(0, 256, (10, 12)).astype(np.int32)
    with StreamingFrontend(fleet=PixieFleet(default_grid=GRID),
                           max_linger_s=0.01) as svc:
        h_chain = svc.submit(CHAIN, img)
        h_single = svc.submit("sobel_x", img)
        np.testing.assert_array_equal(
            np.asarray(h_chain.result(timeout=WAIT)),
            staged_oracle(cfgs, img),
        )
        np.testing.assert_array_equal(
            np.asarray(h_single.result(timeout=WAIT)),
            staged_oracle(cfgs[1:2], img),
        )
        assert h_chain.job().app == "gauss3+sobel_x+threshold"


# -- mesh row sharding (device-gated; CI forces host devices) -----------------


@needs_two_devices
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_fleet_pipeline_rows2_bitwise(backend, rng):
    cfgs = chain_configs()
    images = [rng.integers(0, 256, hw).astype(np.int32)
              for hw in [(24, 16), (17, 13)]]
    fleet = PixieFleet(default_grid=GRID, backend=backend,
                       mesh=MeshSpec(rows=2))
    outs = fleet.run_many(
        [FleetRequest(pipeline=CHAIN, image=im) for im in images]
    )
    assert not fleet.stats.mesh_degraded
    for im, got in zip(images, outs):
        np.testing.assert_array_equal(np.asarray(got),
                                      staged_oracle(cfgs, im))


@pytest.mark.skipif(N_DEVICES < 4, reason="needs >= 4 local devices")
def test_fleet_pipeline_mesh2x2_bitwise(rng):
    cfgs = chain_configs()
    images = [rng.integers(0, 256, (21, 15)).astype(np.int32)
              for _ in range(4)]
    fleet = PixieFleet(default_grid=GRID, mesh=MeshSpec(app=2, rows=2))
    outs = fleet.run_many(
        [FleetRequest(pipeline=CHAIN, image=im) for im in images]
    )
    assert not fleet.stats.mesh_degraded
    for im, got in zip(images, outs):
        np.testing.assert_array_equal(np.asarray(got),
                                      staged_oracle(cfgs, im))
