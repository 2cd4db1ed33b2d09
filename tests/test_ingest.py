"""Fused device-side ingest: line-buffer formation inside the dispatch
must be *bitwise* identical to the host-side two-step oracle
(``applications.stencil_inputs`` + ``interpreter.pack_inputs`` + overlay)
-- across every library app, non-square frames, ragged multi-tenant
batches, and both the single-app and fleet entry points.  The batched
equivalence tests are parametrized over ``backend=xla|pallas`` so the
fused-ingest megakernel (interpret mode off-TPU) cannot drift from the
interpreter oracle without failing PRs."""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import shared_app_grid

from repro.core import map_app, sobel_grid
from repro.core import applications as apps
from repro.core.bitstream import VCGRAConfig
from repro.core.ingest import IngestError, IngestPlan, plan_for, tap_offsets
from repro.core.interpreter import (
    make_batched_fused_overlay_fn,
    make_overlay_fn,
    pack_inputs,
    pad_channels,
    run_app_fused,
)
from repro.runtime.fleet import FleetRequest, PixieFleet

ALL_NAMES = sorted(apps.ALL_APPS)
GRID_ALL = shared_app_grid(ALL_NAMES, name="ingest-shared")


def unfused_reference(grid, cfg, img):
    """The host-side two-step oracle the fused path must match bitwise."""
    taps = apps.stencil_inputs(jnp.asarray(img))
    feed = {k: v for k, v in taps.items() if k in cfg.input_order}
    x = pad_channels(pack_inputs(cfg, feed, grid.dtype), grid.num_inputs)
    y = make_overlay_fn(grid)(cfg.to_jax(), x)
    return np.asarray(y)


# -- plan construction --------------------------------------------------------


def test_plan_layout_and_assemble_attaches_it():
    cfg = map_app(apps.sobel_x(), sobel_grid())
    plan = cfg.ingest
    assert plan is not None and plan.radius == 1
    assert plan.num_taps == 9 and plan.tap_sel.shape == (18,)
    # 9 taps selected, 9 coefficient consts + 0 padding on the 18-wide VC
    assert int((plan.tap_sel < plan.num_taps).sum()) == 9
    offsets = tap_offsets(1)
    for c, name in enumerate(cfg.input_order):
        if name.startswith("p"):
            dj, di = int(name[1]) - 1, int(name[2]) - 1
            assert offsets[plan.tap_sel[c]] == (dj, di)
        else:
            assert plan.tap_sel[c] == plan.zero_row
            assert plan.const_vals[c] == cfg.const_values[name]


def test_plan_rejects_unfeedable_channels_and_overwide_apps():
    with pytest.raises(IngestError, match="neither"):
        plan_for(("p11", "weird"), {}, 4)
    with pytest.raises(ValueError, match="grid has"):
        plan_for(("p11", "p12"), {}, 1)


def test_plan_survives_config_json_roundtrip():
    cfg = map_app(apps.gaussian_blur(), GRID_ALL)
    back = VCGRAConfig.from_json(cfg.to_json())
    assert back.ingest is not None
    np.testing.assert_array_equal(back.ingest.tap_sel, cfg.ingest.tap_sel)
    np.testing.assert_array_equal(back.ingest.const_vals, cfg.ingest.const_vals)
    assert back.ingest.radius == cfg.ingest.radius


def test_plan_stack_rejects_mismatched():
    a = plan_for(("p11",), {}, 4)
    b = plan_for(("p11",), {}, 5)
    with pytest.raises(ValueError, match="does not match"):
        IngestPlan.stack([a, b], jnp.int32)
    with pytest.raises(ValueError, match="empty"):
        IngestPlan.stack([], jnp.int32)


# -- fused == unfused, bitwise ------------------------------------------------


@pytest.mark.parametrize("name", ALL_NAMES)
def test_fused_overlay_matches_unfused_all_apps(name, rng):
    """Every library app, non-square frame: single fused dispatch output
    == stencil_inputs + pack_inputs + overlay, bitwise."""
    img = rng.integers(0, 256, (13, 7)).astype(np.int32)
    cfg = map_app(apps.ALL_APPS[name](), GRID_ALL)
    ref = unfused_reference(GRID_ALL, cfg, img)
    got = np.asarray(run_app_fused(GRID_ALL, cfg, jnp.asarray(img)))
    np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_batched_fused_matches_unfused_ragged(backend, rng):
    """Ragged multi-tenant non-square frames on one zero canvas: each
    [H, W] output slice is bitwise identical to the per-app unfused path,
    on both the XLA interpreter and the Pallas megakernel backends."""
    names = ["sobel_mag", "gauss3", "threshold", "identity", "laplace"]
    hws = [(5, 9), (12, 4), (7, 7), (3, 11), (10, 6)]
    images = [rng.integers(0, 256, hw).astype(np.int32) for hw in hws]
    configs = [map_app(apps.ALL_APPS[n](), GRID_ALL) for n in names]

    Hb = max(h for h, _ in hws)
    Wb = max(w for _, w in hws)
    canvas = np.zeros((len(names), Hb, Wb), dtype=np.int32)
    for i, img in enumerate(images):
        canvas[i, : img.shape[0], : img.shape[1]] = img

    fn = make_batched_fused_overlay_fn(GRID_ALL, backend=backend)
    ys = fn(
        VCGRAConfig.stack(configs),
        IngestPlan.stack([c.ingest for c in configs], GRID_ALL.dtype),
        np.asarray(hws, np.int32),
        jnp.asarray(canvas),
    )
    for i, (cfg, img) in enumerate(zip(configs, images)):
        H, W = img.shape
        got = np.asarray(ys[i]).reshape((-1, Hb, Wb))[:, :H, :W]
        ref = unfused_reference(GRID_ALL, cfg, img).reshape((-1, H, W))
        np.testing.assert_array_equal(got, ref)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_fleet_fused_all_apps_one_flush(backend, rng):
    """The full fleet path (submit raw frames, one fused dispatch) vs the
    sequential unfused oracle, all library apps, ragged non-square sizes,
    on both backends."""
    fleet = PixieFleet(default_grid=GRID_ALL, backend=backend)
    images = [
        rng.integers(0, 256, (5 + 2 * i, 17 - i)).astype(np.int32)
        for i in range(len(ALL_NAMES))
    ]
    outs = fleet.run_many(
        [FleetRequest(app=n, image=i) for n, i in zip(ALL_NAMES, images)]
    )
    assert fleet.stats.dispatches == 1 and fleet.stats.fused_dispatches == 1
    for name, img, y in zip(ALL_NAMES, images, outs):
        cfg = map_app(apps.ALL_APPS[name](), GRID_ALL)
        ref = unfused_reference(GRID_ALL, cfg, img).reshape((-1,) + img.shape)
        np.testing.assert_array_equal(np.atleast_3d(y if y.ndim == 3 else y[None]), ref)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_fleet_mixed_fused_and_channel_requests(backend, rng):
    """A flush mixing raw-frame (fused) and named-channel (packed) requests
    serves both, in two dispatches, all bitwise-exact -- exercising both
    the fused megakernel and the packed batched kernel under pallas."""
    grid = sobel_grid()
    img = rng.integers(0, 256, (6, 9)).astype(np.int32)
    x = rng.integers(0, 256, (23,)).astype(np.int32)
    fleet = PixieFleet(default_grid=grid, backend=backend)
    outs = fleet.run_many([
        FleetRequest(app="sobel_x", image=img),
        FleetRequest(app="threshold", inputs={"p11": x}),
    ])
    assert fleet.stats.dispatches == 2 and fleet.stats.fused_dispatches == 1
    np.testing.assert_array_equal(outs[0], apps.conv2d_reference(img, apps.SOBEL_X))
    np.testing.assert_array_equal(outs[1][0], (x > 128).astype(np.int32))


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_fused_compile_once_across_apps_and_shapes(backend, rng):
    """One fused executable serves every app (plans are runtime settings);
    pow-2 canvas bucketing keeps repeat flushes on it -- the compile-once
    contract holds identically for the pallas megakernel backend."""
    fleet = PixieFleet(default_grid=GRID_ALL, batch_tile=4, backend=backend)
    img = rng.integers(0, 256, (9, 9)).astype(np.int32)
    for names in (["sobel_x", "gauss3"], ["laplace", "identity"], ["sharpen"]):
        fleet.run_many([FleetRequest(app=n, image=img) for n in names])
    assert fleet.stats.overlay_builds == 1
    assert fleet.overlay_executable_count(GRID_ALL) in (1, -1)
    # a repeat tenant set also reuses the stacked settings+ingest bank
    fleet.run_many([FleetRequest(app=n, image=img) for n in ["sobel_x", "gauss3"]])
    assert fleet.stats.stack_bank_hits >= 1


def test_fused_timings_split(rng):
    """The host-side stamps a flush leaves: cumulative ``pack_s`` (intake,
    embed, bank and ship), and the last flush's start and wall time."""
    fleet = PixieFleet(default_grid=sobel_grid())
    img = rng.integers(0, 256, (8, 8)).astype(np.int32)
    packs = []
    for _ in range(2):
        t0 = time.perf_counter()
        fleet.run_many([FleetRequest(app="sobel_x", image=img)])
        t1 = time.perf_counter()
        assert set(fleet.timings) == {"pack_s", "flush_started", "flush_s"}
        started, flush_s = (fleet.timings["flush_started"],
                            fleet.timings["flush_s"])
        assert t0 <= started and 0 < flush_s <= t1 - started
        packs.append(fleet.timings["pack_s"])
    assert 0 < packs[0] < packs[1]


# -- satellite regressions ----------------------------------------------------


def test_pack_inputs_all_const_raises_or_takes_batch_shape():
    """An all-const channel set used to silently produce a scalar () batch
    (which the fleet then rejected with an unrelated shape error); now it
    raises a clear error unless the caller pins the batch shape."""
    from repro.core import DFG, for_dfg

    g = DFG("allconst")
    g.output(g.add(g.const("a", 3), g.const("b", 4)))
    grid = for_dfg(g, shape="exact")
    cfg = map_app(g, grid)
    with pytest.raises(ValueError, match="batch_shape"):
        pack_inputs(cfg, {}, grid.dtype)
    x = pack_inputs(cfg, {}, grid.dtype, batch_shape=(4,))
    assert x.shape == (len(cfg.input_order), 4)
    np.testing.assert_array_equal(np.asarray(x[0]), np.full((4,), 3))
    # the fleet surfaces the same clear error at submit time
    fleet = PixieFleet(default_grid=grid)
    with pytest.raises(ValueError, match="batch_shape"):
        fleet.submit(FleetRequest(app=g, inputs={}))


def test_fleet_result_eviction_error_names_ticket_and_bound(rng):
    img = rng.integers(0, 256, (4, 4)).astype(np.int32)
    fleet = PixieFleet(default_grid=sobel_grid(), max_retained_results=1)
    t0 = fleet.submit(FleetRequest(app="identity", image=img))
    t1 = fleet.submit(FleetRequest(app="identity", image=img))
    fleet.flush()  # retains only t1; t0 evicted by the bound
    with pytest.raises(KeyError, match=rf"ticket {t0}.*max_retained_results=1"):
        fleet.result(t0)
    np.testing.assert_array_equal(fleet.result(t1), img)
    with pytest.raises(KeyError, match="already redeemed"):
        fleet.result(t1)
