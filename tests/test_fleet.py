"""Multi-tenant batched overlay tests: N stacked configs must be bitwise
identical to N sequential `Pixie` runs -- including ragged/padded batches,
tile padding on the app axis, config-cache hits, and the compile-once-per-
GridSpec invariant.  The bitwise-equivalence tests are parametrized over
``backend=xla|pallas`` so drift between the jnp interpreter and the
batched Pallas megakernels (interpret mode off-TPU) fails PRs."""

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import shared_app_grid

from repro.core import Pixie, map_app, sobel_grid
from repro.core import applications as apps
from repro.core.bitstream import VCGRAConfig
from repro.core.interpreter import (
    make_batched_overlay_fn, make_overlay_fn, pack_inputs, pad_channels,
)
from repro.runtime.fleet import FleetRequest, LRUCache, PixieFleet
from repro.serve.fleet_frontend import FleetFrontend

# The ISSUE's demonstrator trio: Sobel + threshold + blur.  gauss3 needs 19
# memory channels (9 taps + 9 coeffs + divisor), more than the paper's
# 18-input Sobel grid, so the shared fleet grid is generated from the
# union of the three apps' demands (the paper's "application specific grid
# designs", Sec. III-C).
TRIO = ["sobel_x", "threshold", "gauss3"]


def shared_grid(app_names):
    return shared_app_grid(app_names, name="fleet-shared")


def sequential_reference(grid, app_names, images):
    outs = []
    for name, img in zip(app_names, images):
        pix = Pixie(grid, mode="conventional")
        pix.load(map_app(apps.ALL_APPS[name](), grid))
        outs.append(np.asarray(pix.run_image(jnp.asarray(img))))
    return outs


# -- core: stacked configs through the batched interpreter --------------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_stacked_configs_match_sequential_bitwise(backend, rng):
    grid = shared_grid(TRIO)
    img = rng.integers(0, 256, (11, 14)).astype(np.int32)
    ref = sequential_reference(grid, TRIO, [img] * len(TRIO))

    configs, xs = [], []
    taps = apps.stencil_inputs(jnp.asarray(img))
    for name in TRIO:
        cfg = map_app(apps.ALL_APPS[name](), grid)
        feed = {k: v for k, v in taps.items() if k in cfg.input_order}
        configs.append(cfg)
        xs.append(pad_channels(pack_inputs(cfg, feed, grid.dtype), grid.num_inputs))

    fn = make_batched_overlay_fn(grid, backend=backend)
    ys = fn(VCGRAConfig.stack(configs), jnp.stack(xs))
    for i in range(len(TRIO)):
        np.testing.assert_array_equal(
            np.asarray(ys[i, 0]).reshape(img.shape), ref[i]
        )


def test_make_batched_overlay_fn_rejects_unknown_backend():
    with pytest.raises(ValueError, match="unknown backend"):
        make_batched_overlay_fn(sobel_grid(), backend="cuda")
    with pytest.raises(ValueError, match="unknown backend"):
        PixieFleet(backend="cuda")


def test_batched_equals_unbatched_overlay(rng):
    """The batched executor is exactly vmap(overlay): per-app slices agree
    with the sequential compile-once interpreter on the same grid."""
    grid = sobel_grid()
    names = ["sobel_x", "sobel_y", "sharpen", "laplace"]
    img = rng.integers(0, 256, (9, 9)).astype(np.int32)
    taps = apps.stencil_inputs(jnp.asarray(img))
    overlay = make_overlay_fn(grid)

    configs, xs = [], []
    for name in names:
        cfg = map_app(apps.ALL_APPS[name](), grid)
        feed = {k: v for k, v in taps.items() if k in cfg.input_order}
        configs.append(cfg)
        xs.append(pad_channels(pack_inputs(cfg, feed, grid.dtype), grid.num_inputs))

    ys = make_batched_overlay_fn(grid)(VCGRAConfig.stack(configs), jnp.stack(xs))
    for cfg, x, y in zip(configs, xs, ys):
        np.testing.assert_array_equal(np.asarray(y), np.asarray(overlay(cfg.to_jax(), x)))


def test_stack_rejects_mismatched_grids():
    g_small = apps.threshold()
    cfg_a = map_app(apps.sobel_x(), sobel_grid())
    from repro.core import for_dfg

    cfg_b = map_app(g_small, for_dfg(g_small, shape="exact"))
    with pytest.raises(ValueError, match="does not match"):
        VCGRAConfig.stack([cfg_a, cfg_b])
    with pytest.raises(ValueError, match="empty"):
        VCGRAConfig.stack([])


def test_stack_shapes():
    grid = sobel_grid()
    configs = [map_app(apps.sobel_x(), grid), map_app(apps.sobel_y(), grid)]
    opcodes, selects, out_sel = VCGRAConfig.stack(configs)
    assert len(opcodes) == grid.num_levels
    for lvl in range(grid.num_levels):
        assert opcodes[lvl].shape == (2, grid.pes_per_level[lvl])
        assert selects[lvl].shape == (2, grid.pes_per_level[lvl], 2)
    assert out_sel.shape == (2, grid.num_outputs)


# -- Pixie.run_many -----------------------------------------------------------


def test_run_many_matches_sequential_ragged(rng):
    """Ragged pixel batches (different image sizes) padded to one tile must
    slice back to exactly the sequential outputs."""
    grid = sobel_grid()
    names = ["sobel_x", "sobel_y", "laplace"]
    images = [
        rng.integers(0, 256, hw).astype(np.int32)
        for hw in [(7, 9), (12, 5), (4, 4)]
    ]
    ref = sequential_reference(grid, names, images)

    pix = Pixie(grid, mode="conventional")
    requests = []
    for name, img in zip(names, images):
        dfg = apps.ALL_APPS[name]()
        taps = apps.stencil_inputs(jnp.asarray(img))
        feed = {k: v for k, v in taps.items() if k in dfg.inputs}
        requests.append((dfg, feed))
    outs = pix.run_many(requests)
    for img, y, r in zip(images, outs, ref):
        assert y.shape == (1, img.size)
        np.testing.assert_array_equal(np.asarray(y[0]).reshape(img.shape), r)

    # explicit batch_pad beyond the largest request is also exact
    outs = pix.run_many(requests, batch_pad=256)
    for img, y, r in zip(images, outs, ref):
        np.testing.assert_array_equal(np.asarray(y[0]).reshape(img.shape), r)

    with pytest.raises(ValueError, match="batch_pad"):
        pix.run_many(requests, batch_pad=3)


def test_run_many_requires_conventional():
    pix = Pixie(sobel_grid(), mode="parameterized")
    with pytest.raises(RuntimeError, match="conventional"):
        pix.run_many([(apps.sobel_x(), {})])
    assert Pixie(sobel_grid()).run_many([]) == []


# -- the fleet scheduler ------------------------------------------------------


def test_fleet_trio_bitwise_and_cache_counters(rng):
    grid = shared_grid(TRIO)
    img = rng.integers(0, 256, (10, 13)).astype(np.int32)
    ref = sequential_reference(grid, TRIO, [img] * len(TRIO))

    fleet = PixieFleet(default_grid=grid, batch_tile=4)
    outs = fleet.run_many([FleetRequest(app=n, image=img) for n in TRIO])
    for y, r in zip(outs, ref):
        np.testing.assert_array_equal(y, r)

    s = fleet.stats
    assert s.map_calls == 3 and s.config_cache_hits == 0
    assert s.overlay_builds == 1
    assert s.padded_app_slots == 1  # 3 requests -> tile of 4

    # repeat tenants: no new place/route, no new overlay, no new executable
    outs2 = fleet.run_many([FleetRequest(app=n, image=img) for n in TRIO])
    for y, r in zip(outs2, ref):
        np.testing.assert_array_equal(y, r)
    s = fleet.stats
    assert s.map_calls == 3 and s.config_cache_hits == 3
    assert s.overlay_builds == 1 and s.overlay_cache_hits == 1
    assert s.stack_bank_hits == 1  # settings bank reused, not re-stacked
    # compile-once per GridSpec (-1 = jax without jit-cache introspection)
    assert fleet.overlay_executable_count(grid) in (1, -1)
    # run_many redeems everything: nothing retained, nothing leaked
    assert len(fleet._results) == 0


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_fleet_ragged_images_one_flush(backend, rng):
    grid = sobel_grid()
    names = ["sobel_x", "sharpen", "identity"]
    images = [
        rng.integers(0, 256, hw).astype(np.int32)
        for hw in [(6, 8), (11, 11), (3, 5)]
    ]
    ref = sequential_reference(grid, names, images)
    fleet = PixieFleet(default_grid=grid, backend=backend)
    outs = fleet.run_many(
        [FleetRequest(app=n, image=i) for n, i in zip(names, images)]
    )
    assert fleet.stats.dispatches == 1
    assert fleet.stats.backend == backend
    for y, r in zip(outs, ref):
        np.testing.assert_array_equal(y, r)


def test_fleet_groups_by_grid(rng):
    """Requests on different grids execute in separate dispatches but one
    flush; per-request grid override routes around the default."""
    img = rng.integers(0, 256, (5, 7)).astype(np.int32)
    g3 = apps.gaussian_blur()
    from repro.core import for_dfg

    gg = for_dfg(g3, shape="exact")
    fleet = PixieFleet(default_grid=sobel_grid())
    outs = fleet.run_many([
        FleetRequest(app="sobel_x", image=img),
        FleetRequest(app=g3, image=img, grid=gg),
    ])
    assert fleet.stats.dispatches == 2 and fleet.stats.overlay_builds == 2
    np.testing.assert_array_equal(outs[0], apps.conv2d_reference(img, apps.SOBEL_X))
    np.testing.assert_array_equal(
        outs[1], apps.conv2d_reference(img, apps.GAUSS3, divisor=16.0)
    )


def test_fleet_channel_requests_and_validation(rng):
    grid = sobel_grid()
    dfg = apps.threshold()
    x = rng.integers(0, 256, (17,)).astype(np.int32)
    fleet = PixieFleet(default_grid=grid)
    (out,) = fleet.run_many([FleetRequest(app=dfg, inputs={"p11": x})])
    np.testing.assert_array_equal(out[0], (x > 128).astype(np.int32))

    with pytest.raises(ValueError, match="exactly one"):
        fleet.submit(FleetRequest(app=dfg))
    with pytest.raises(ValueError, match="exactly one"):
        fleet.submit(FleetRequest(app=dfg, inputs={"p11": x}, image=x.reshape(1, -1)))


def test_bad_submit_cannot_poison_queued_peers(rng):
    """An unmappable app (or missing input) raises at submit() and must
    leave previously queued tenants untouched."""
    grid = sobel_grid()
    img = rng.integers(0, 256, (6, 6)).astype(np.int32)
    fleet = PixieFleet(default_grid=grid)
    t = fleet.submit(FleetRequest(app="sobel_x", image=img))
    from repro.core.place import PlacementError

    with pytest.raises(PlacementError):  # gauss3 needs 19 inputs, grid has 18
        fleet.submit(FleetRequest(app="gauss3", image=img))
    with pytest.raises(KeyError):        # missing channel input
        fleet.submit(FleetRequest(app="threshold", inputs={"wrong": img.ravel()}))
    outs = fleet.flush()
    np.testing.assert_array_equal(
        outs[t], apps.conv2d_reference(img, apps.SOBEL_X)
    )


def test_wrong_grid_config_rejected_at_submit(rng):
    """A pre-mapped config for ANOTHER grid must be rejected at submit()
    (it would otherwise blow up VCGRAConfig.stack at flush time and drop
    queued peers)."""
    from repro.core import for_dfg

    grid = sobel_grid()
    img = rng.integers(0, 256, (5, 5)).astype(np.int32)
    thr = apps.threshold()
    foreign_cfg = map_app(thr, for_dfg(thr, shape="exact"))
    fleet = PixieFleet(default_grid=grid)
    t = fleet.submit(FleetRequest(app="sobel_x", image=img))
    with pytest.raises(ValueError, match="does not match"):
        fleet.submit(FleetRequest(app=foreign_cfg, inputs={"p11": img.ravel()}))
    outs = fleet.flush()
    np.testing.assert_array_equal(outs[t], apps.conv2d_reference(img, apps.SOBEL_X))


def test_run_many_larger_than_retention_cap(rng):
    """run_many consumes flush()'s return value directly, so batches larger
    than max_retained_results must still return every output."""
    img = rng.integers(0, 256, (4, 4)).astype(np.int32)
    fleet = PixieFleet(default_grid=sobel_grid(), max_retained_results=2)
    outs = fleet.run_many([FleetRequest(app="identity", image=img)] * 6)
    assert len(outs) == 6
    for y in outs:
        np.testing.assert_array_equal(y, img)
    assert len(fleet._results) == 0


def test_lru_cache_eviction_and_counters():
    c = LRUCache(2)
    c.put("a", 1); c.put("b", 2)
    assert c.get("a") == 1 and c.hits == 1
    c.put("c", 3)               # evicts "b" (LRU)
    assert "b" not in c and "a" in c
    assert c.get("b") is None and c.misses == 1
    assert c.evictions == 1
    with pytest.raises(ValueError):
        LRUCache(0)


def test_structural_hash_keys_repeat_tenants():
    assert apps.sobel_x().structural_hash() == apps.sobel_x().structural_hash()
    assert apps.sobel_x().structural_hash() != apps.sobel_y().structural_hash()
    # coefficient values are part of the identity (threshold level matters)
    assert (
        apps.threshold(100.0).structural_hash()
        != apps.threshold(200.0).structural_hash()
    )


# -- serve front-end ----------------------------------------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_frontend_process_batch_order_and_stats(backend, rng):
    img = rng.integers(0, 256, (8, 8)).astype(np.int32)
    svc = FleetFrontend(fleet=PixieFleet(default_grid=sobel_grid(),
                                         backend=backend))
    names = ["sobel_y", "identity", "sobel_x"]
    outs = svc.process_batch([(n, img) for n in names])
    ref = sequential_reference(sobel_grid(), names, [img] * 3)
    for y, r in zip(outs, ref):
        np.testing.assert_array_equal(y, r)
    assert svc.stats.dispatches == 1
    assert svc.backend == backend

    with pytest.raises(KeyError, match="unknown app"):
        svc.submit("not_an_app", img)
    assert "sobel_x" in svc.available_apps()


def test_frontend_backend_kwarg_and_conflict(rng):
    svc = FleetFrontend(backend="pallas")
    assert svc.backend == "pallas" and svc.fleet.backend == "pallas"
    with pytest.raises(ValueError, match="conflicts"):
        FleetFrontend(fleet=PixieFleet(backend="xla"), backend="pallas")
    # invalid names fail with the shared unknown-backend error, not a
    # misleading conflict message (and "" is rejected, not coerced to xla)
    with pytest.raises(ValueError, match="unknown backend"):
        FleetFrontend(fleet=PixieFleet(), backend="cuda")
    with pytest.raises(ValueError, match="unknown backend"):
        FleetFrontend(backend="")


def test_frontend_flush_latency_accounting(rng):
    img = rng.integers(0, 256, (4, 6)).astype(np.int32)
    svc = FleetFrontend(fleet=PixieFleet(default_grid=sobel_grid()))
    h = svc.submit("laplace", img)
    jobs = svc.flush()
    assert [j.ticket for j in jobs] == [h.ticket]
    assert jobs[0].app == "laplace"
    # the PR 6 latency split: queue wait and flush time are separate
    assert jobs[0].queue_s >= 0 and jobs[0].flush_s > 0
    assert jobs[0].latency_s == pytest.approx(jobs[0].queue_s + jobs[0].flush_s)
    np.testing.assert_array_equal(
        h.result(), apps.conv2d_reference(img, apps.LAPLACE)
    )


# -- async (double-buffered) ingest -------------------------------------------


def test_async_pallas_partial_tile_matches_numpy(rng):
    """A partial app tile of frames in 1080p's proportions (18 x 32 on a
    32 x 32 canvas in row tiles of 8): the megakernel skips each frame's
    last tile and the padded slot, and every output still equals the
    numpy reference; the fleet counts 9 live of 16 steps."""
    kernels = {"sobel_x": apps.SOBEL_X, "sobel_y": apps.SOBEL_Y,
               "laplace": apps.LAPLACE}
    images = [rng.integers(0, 256, (18, 32)).astype(np.int32)
              for _ in kernels]
    fleet = PixieFleet(default_grid=sobel_grid(), backend="pallas",
                       ingest="async", batch_tile=4, tile_rows=8)
    got = fleet.run_many([FleetRequest(app=n, image=im)
                          for n, im in zip(kernels, images)])
    for k, im, y in zip(kernels.values(), images, got):
        np.testing.assert_array_equal(np.asarray(y),
                                      apps.conv2d_reference(im, k))
    assert fleet.stats.padded_app_slots == 1
    assert (fleet.stats.kernel_steps, fleet.stats.kernel_steps_live) == (
        16, 9)
    assert all("|n4x32x32" in k for k in fleet.stats.dispatch_plans)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_async_ingest_bitwise_mixed_flushes(backend, rng):
    """ingest="async" == ingest="sync", bitwise, under repeated mixed
    fused/channel flushes -- the double-buffered pipeline (pooled donated
    canvases, lazy output slicing) changes buffer lifetime only, never
    values.  The repeat flushes exercise the canvas pool rotation while
    the previous dispatch's lazy outputs may still be in flight."""
    grid = sobel_grid()
    images = [rng.integers(0, 256, hw).astype(np.int32)
              for hw in [(6, 9), (11, 5), (3, 8)]]
    x = rng.integers(0, 256, (23,)).astype(np.int32)
    reqs = [FleetRequest(app=n, image=i)
            for n, i in zip(["sobel_x", "sharpen", "identity"], images)]
    reqs.append(FleetRequest(app="threshold", inputs={"p11": x}))

    ref = PixieFleet(default_grid=grid, backend=backend).run_many(reqs)
    fleet = PixieFleet(default_grid=grid, backend=backend, ingest="async")
    for _ in range(3):
        got = fleet.run_many(reqs)
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert fleet.stats.ingest == "async"
    # round 2+ reuse the pooled canvas instead of allocating
    assert fleet.stats.canvas_pool_hits >= 1
    # every dispatch is stamped with the async plan key segment
    assert all("async" in k for k in fleet.stats.dispatch_plans)


def test_frontend_ingest_kwarg_and_conflict(rng):
    svc = FleetFrontend(ingest="async")
    assert svc.ingest == "async" and svc.fleet.ingest == "async"
    img = rng.integers(0, 256, (4, 6)).astype(np.int32)
    np.testing.assert_array_equal(
        np.asarray(svc.process("laplace", img)),
        apps.conv2d_reference(img, apps.LAPLACE),
    )
    with pytest.raises(ValueError, match="conflicts"):
        FleetFrontend(fleet=PixieFleet(ingest="sync"), ingest="async")
    with pytest.raises(ValueError, match="unknown ingest"):
        FleetFrontend(ingest="dma")
