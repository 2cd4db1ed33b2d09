"""2-D (app x rows) mesh scale-out: MeshSpec API + halo-exchange parity.

The row axis shards a fused frame into contiguous pixel-row bands; the
radius-wide seam halo is exchanged with ``jax.lax.ppermute`` inside
``shard_map`` (``parallel.axes.shard_apps_rows``) and the unchanged
per-shard executor runs on the haloed band as if it were a short frame,
so every sharded output must be BITWISE equal to the single-device run.
The parity matrix here covers ragged, non-square, mixed-app stacks for
``backend=xla|pallas`` x ``ingest=sync|async``, rows that do not divide
the padded tile height, and radius 0 (no collective emitted -- asserted
on the jaxpr).  CI's mesh2d-parity job forces four host devices with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``; on fewer devices
the mesh tests skip and the MeshSpec API tests still run.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    MeshSpec, OverlayPlan, Pixie, compile_plan, map_app, sobel_grid,
)
from repro.core import applications as apps
from repro.core.bitstream import VCGRAConfig
from repro.core.ingest import IngestPlan
from repro.core.tiling import row_band
from repro.parallel.axes import build_mesh, halo_exchange_rows
from repro.runtime.fleet import FleetRequest, PixieFleet
from repro.serve import FleetFrontend, StreamingFrontend

GRID = sobel_grid()
N_DEVICES = len(jax.local_devices())
needs_two_devices = pytest.mark.skipif(
    N_DEVICES < 2, reason="needs >= 2 local devices"
)
needs_four_devices = pytest.mark.skipif(
    N_DEVICES < 4,
    reason="needs >= 4 local devices (CI mesh2d-parity job forces 4 via "
    "XLA_FLAGS=--xla_force_host_platform_device_count=4)",
)

# Ragged, non-square, mixed-app: the canonical 2-D parity workload.
NAMES = ("sobel_x", "threshold", "sobel_y", "identity")
HWS = ((13, 17), (8, 8), (21, 9), (5, 30))


def _stacked_workload(rng, names=NAMES, hws=HWS):
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


# -- MeshSpec API -------------------------------------------------------------


def test_meshspec_validation_and_identity():
    assert MeshSpec() == MeshSpec(app=1, rows=1)
    assert MeshSpec(app=2, rows=3).size == 6
    assert MeshSpec(app=2, rows=3).shape() == (2, 3)
    assert MeshSpec(app=2, rows=3).app_only() == MeshSpec(app=2)
    assert str(MeshSpec(app=2, rows=3)) == "2x3"
    # frozen + hashable: usable directly as a cache-key component
    assert len({MeshSpec(), MeshSpec(app=1), MeshSpec(rows=2)}) == 2
    with pytest.raises(ValueError, match="app"):
        MeshSpec(app=0)
    with pytest.raises(ValueError, match="rows"):
        MeshSpec(rows=-1)
    with pytest.raises(ValueError, match="rows"):
        MeshSpec(rows=True)
    with pytest.raises(Exception):
        MeshSpec(app=2).app = 3  # frozen


def test_row_band_floors():
    assert row_band(16, 4) == 4
    assert row_band(13, 4) == 4          # ceil
    assert row_band(2, 4) == 1           # H < rows still gives bands
    assert row_band(16, 4, radius=7) == 7  # radius floor: one-hop halo
    assert row_band(1, 1) == 1


def test_plan_key_backward_compat_and_cache_identity():
    """MeshSpec(app=k) keys exactly like the pre-2-D device count: old
    dev2 executable populations are reused, and the deprecated spelling
    IS the new plan (one hash, one LRU entry)."""
    via_mesh = OverlayPlan(grid=GRID, batched=True, fused=True,
                           mesh=MeshSpec(app=2))
    with pytest.warns(DeprecationWarning, match="MeshSpec"):
        via_devices = OverlayPlan(grid=GRID, batched=True, fused=True,
                                  devices=2)
    assert via_mesh == via_devices
    assert hash(via_mesh) == hash(via_devices)
    assert via_mesh.key() == via_devices.key()
    assert "dev2" in via_mesh.key() and "rows" not in via_mesh.key()
    # the rows axis is a NEW key segment, appended only when active
    plan2d = OverlayPlan(grid=GRID, batched=True, fused=True,
                         mesh=MeshSpec(app=2, rows=2))
    assert "dev2" in plan2d.key() and "rows2" in plan2d.key()
    assert plan2d != via_mesh


def test_plan_mesh_validation():
    with pytest.raises(ValueError, match="MeshSpec"):
        OverlayPlan(grid=GRID, batched=True, mesh=2)
    with pytest.raises(ValueError, match="batched"):
        OverlayPlan(grid=GRID, mesh=MeshSpec(app=2))
    with pytest.raises(ValueError, match="fused"):
        OverlayPlan(grid=GRID, batched=True, fused=False,
                    mesh=MeshSpec(rows=2))
    with pytest.raises(ValueError, match="not both"):
        OverlayPlan(grid=GRID, batched=True, mesh=MeshSpec(app=2), devices=2)


def test_deprecated_devices_shims_warn_everywhere():
    with pytest.warns(DeprecationWarning, match="MeshSpec"):
        fleet = PixieFleet(default_grid=GRID, devices=1)
    assert fleet.mesh == MeshSpec()
    with pytest.warns(DeprecationWarning, match="MeshSpec"):
        pix = Pixie(GRID, devices=1)
    assert pix.devices == 1 and pix.mesh == MeshSpec()
    with pytest.warns(DeprecationWarning, match="MeshSpec"):
        svc = FleetFrontend(devices=1)
    assert svc.devices == 1 and svc.mesh == MeshSpec()
    with pytest.raises(ValueError, match="not both"):
        PixieFleet(default_grid=GRID, mesh=MeshSpec(), devices=1)
    with pytest.raises(ValueError, match="rows"):
        Pixie(GRID, mesh=MeshSpec(rows=2))


# -- halo exchange ------------------------------------------------------------


def test_radius_zero_emits_no_collective():
    """Radius-0 row sharding is pure data parallelism: the halo helper is
    the identity and no ppermute appears in the lowered jaxpr."""
    slab = jnp.ones((2, 4, 8), jnp.int32)
    assert halo_exchange_rows(slab, 0, rows=4) is slab
    jaxpr = str(jax.make_jaxpr(
        lambda s: halo_exchange_rows(s, 0, rows=4))(slab))
    assert "ppermute" not in jaxpr
    # and radius > 0 DOES exchange (the negative control)
    mesh = build_mesh(MeshSpec(rows=2))
    if mesh is not None:
        from repro.parallel.axes import _shard_map
        from jax.sharding import PartitionSpec as P
        fn = _shard_map(
            lambda s: halo_exchange_rows(s, 1, rows=2),
            mesh=mesh, in_specs=P(None, "rows"), out_specs=P(None, "rows"),
        )
        assert "ppermute" in str(jax.make_jaxpr(fn)(slab))


@needs_two_devices
def test_halo_exchange_matches_neighbor_rows():
    """Each shard's halo is literally its neighbours' edge rows (zeros at
    the frame border), i.e. form_tap_bank's zero-pad semantics."""
    from jax.sharding import PartitionSpec as P
    from repro.parallel.axes import _shard_map

    mesh = build_mesh(MeshSpec(rows=2))
    full = jnp.arange(2 * 8 * 4, dtype=jnp.int32).reshape(2, 8, 4)
    r = 2
    fn = _shard_map(
        lambda s: halo_exchange_rows(s, r, rows=2),
        mesh=mesh, in_specs=P(None, "rows"), out_specs=P(None, "rows"),
    )
    haloed = np.asarray(jax.jit(fn)(full))
    # output is [2, 2*(band+2r), 4] reassembled along the rows axis
    band = 4
    top, bot = (haloed[:, : band + 2 * r, :],
                haloed[:, band + 2 * r:, :])
    np.testing.assert_array_equal(top[:, :r], 0)           # frame border
    np.testing.assert_array_equal(top[:, r:r + band], full[:, :band])
    np.testing.assert_array_equal(top[:, r + band:], full[:, band:band + r])
    np.testing.assert_array_equal(bot[:, :r], full[:, band - r:band])
    np.testing.assert_array_equal(bot[:, r:r + band], full[:, band:])
    np.testing.assert_array_equal(bot[:, r + band:], 0)    # frame border


# -- compiled-plan parity -----------------------------------------------------


def _plan_outputs(workload, spec, backend, tile_rows=None):
    stacked, ingests, canvas = workload
    plan = OverlayPlan(grid=GRID, batched=True, fused=True, radius=1,
                       backend=backend, mesh=spec, tile_rows=tile_rows)
    hw = np.asarray(HWS, np.int32)
    return np.asarray(compile_plan(plan)(stacked, ingests, hw, canvas))


@needs_four_devices
@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("spec", [
    MeshSpec(app=2, rows=2),
    MeshSpec(rows=4),
    MeshSpec(rows=3),      # rows does not divide the 21-row canvas
    MeshSpec(app=4),
], ids=str)
def test_plan_parity_2d_vs_single_device(backend, spec):
    workload = _stacked_workload(np.random.default_rng(0))
    base = _plan_outputs(workload, MeshSpec(), backend)
    got = _plan_outputs(workload, spec, backend)
    np.testing.assert_array_equal(base, got)


@needs_four_devices
def test_plan_parity_with_row_tiling():
    """Row sharding composes with in-shard row tiling (PR 7's pipeline
    runs unchanged within each band)."""
    workload = _stacked_workload(np.random.default_rng(1))
    base = _plan_outputs(workload, MeshSpec(), "pallas", tile_rows=3)
    got = _plan_outputs(workload, MeshSpec(app=2, rows=2), "pallas",
                        tile_rows=3)
    np.testing.assert_array_equal(base, got)


# -- fleet-level parity (the serving path) ------------------------------------


def _fleet_results(rng, spec, backend, ingest):
    frames = [rng.integers(0, 256, hw).astype(np.int32) for hw in HWS]
    fleet = PixieFleet(default_grid=GRID, backend=backend, mesh=spec,
                       ingest=ingest, batch_tile=1)
    tickets = [fleet.submit(FleetRequest(app=n, image=f))
               for n, f in zip(NAMES, frames)]
    res = fleet.flush()
    return [np.asarray(res[t]) for t in tickets], fleet


@needs_four_devices
@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("ingest", ["sync", "async"])
def test_fleet_parity_2d(backend, ingest):
    base, _ = _fleet_results(np.random.default_rng(0), MeshSpec(),
                             backend, ingest)
    got, fleet = _fleet_results(np.random.default_rng(0),
                                MeshSpec(app=2, rows=2), backend, ingest)
    for b, g in zip(base, got):
        np.testing.assert_array_equal(b, g)
    assert fleet.stats.mesh_granted == (2, 2)
    assert not fleet.stats.mesh_degraded
    assert any("rows2" in k for k in fleet.stats.dispatch_plans)


@needs_two_devices
def test_fleet_parity_deprecated_devices_path():
    """The deprecated bare-count spelling warns but stays bitwise-equal
    and reuses the SAME plan population as MeshSpec(app=k)."""
    rng = np.random.default_rng(0)
    base, _ = _fleet_results(rng, MeshSpec(), "xla", "sync")
    rng = np.random.default_rng(0)
    got, fleet_mesh = _fleet_results(rng, MeshSpec(app=2), "xla", "sync")
    rng = np.random.default_rng(0)
    frames = [rng.integers(0, 256, hw).astype(np.int32) for hw in HWS]
    with pytest.warns(DeprecationWarning, match="MeshSpec"):
        fleet_legacy = PixieFleet(default_grid=GRID, backend="xla",
                                  devices=2, batch_tile=1)
    tickets = [fleet_legacy.submit(FleetRequest(app=n, image=f))
               for n, f in zip(NAMES, frames)]
    res = fleet_legacy.flush()
    legacy = [np.asarray(res[t]) for t in tickets]
    for b, g, l in zip(base, got, legacy):
        np.testing.assert_array_equal(b, g)
        np.testing.assert_array_equal(b, l)
    assert fleet_legacy.mesh == MeshSpec(app=2)
    assert set(fleet_legacy.stats.dispatch_plans) == set(
        fleet_mesh.stats.dispatch_plans
    )


def test_fleet_mesh_degradation_is_recorded():
    """A spec the host cannot honor degrades to the bitwise single-device
    fallback AND says so in the stats (truthful dashboards)."""
    spec = MeshSpec(app=N_DEVICES + 1, rows=4)
    fleet = PixieFleet(default_grid=GRID, mesh=spec)
    assert fleet.stats.mesh_requested == spec.shape()
    assert fleet.stats.mesh_granted == (1, 1)
    assert fleet.stats.mesh_degraded
    img = np.arange(64, dtype=np.int32).reshape(8, 8)
    t = fleet.submit(FleetRequest(app="sobel_x", image=img))
    ref = PixieFleet(default_grid=GRID)
    t_ref = ref.submit(FleetRequest(app="sobel_x", image=img))
    np.testing.assert_array_equal(fleet.flush()[t], ref.flush()[t_ref])
    granted = PixieFleet(default_grid=GRID, mesh=MeshSpec())
    assert not granted.stats.mesh_degraded


@needs_four_devices
def test_streaming_frontend_on_2d_mesh(rng):
    img = rng.integers(0, 256, (16, 16)).astype(np.int32)
    ref = np.asarray(FleetFrontend().submit("sobel_x", img).result())
    with StreamingFrontend(mesh=MeshSpec(app=2, rows=2)) as svc:
        assert svc.mesh == MeshSpec(app=2, rows=2)
        got = np.asarray(svc.submit("sobel_x", img).result(timeout=60.0))
    np.testing.assert_array_equal(ref, got)


def test_frontend_mesh_conflict_and_shim():
    fleet = PixieFleet(default_grid=GRID, mesh=MeshSpec())
    with pytest.raises(ValueError, match="conflicts"):
        FleetFrontend(fleet=fleet, mesh=MeshSpec(app=2))
    with pytest.raises(ValueError, match="not both"):
        FleetFrontend(mesh=MeshSpec(), devices=1)


# The hypothesis property sweep over (H, W, radius, app, rows) lives in
# test_mesh2d_property.py, gated on the dev dependency (repo idiom: the
# deterministic matrix above runs even without hypothesis installed).
