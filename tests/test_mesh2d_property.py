"""Property-based tests (hypothesis): 2-D (app x rows) mesh-sharded fused
dispatch must be bitwise identical to the single-device run for *random*
``(H, W, radius, app, rows)`` -- including rows that do not divide H,
bands shorter than the radius, and radius-0 (no halo exchange at all).

The deterministic edge-case matrix twin lives in test_mesh2d.py and runs
even without the dev dependency.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

# Gate rather than hard-import: hypothesis is a dev dependency
# (requirements-dev.txt), absent from minimal runtime installs.
pytest.importorskip(
    "hypothesis", reason="hypothesis not installed (see requirements-dev.txt)"
)
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import MeshSpec, OverlayPlan, compile_plan, map_app, sobel_grid  # noqa: E402
from repro.core import applications as apps  # noqa: E402
from repro.core.bitstream import VCGRAConfig  # noqa: E402
from repro.core.ingest import IngestPlan  # noqa: E402

GRID = sobel_grid()
N_DEVICES = len(jax.local_devices())
needs_two_devices = pytest.mark.skipif(
    N_DEVICES < 2, reason="needs >= 2 local devices"
)
# Mapped settings are shape-independent; build them once for the sweep.
_CONFIGS = None


def _workload(H, W, seed):
    global _CONFIGS
    if _CONFIGS is None:
        configs = [map_app(apps.ALL_APPS[n](), GRID)
                   for n in ("sobel_x", "threshold")]
        _CONFIGS = (VCGRAConfig.stack(configs),
                    IngestPlan.stack([c.ingest for c in configs], GRID.dtype))
    rng = np.random.default_rng(seed)
    canvas = rng.integers(0, 256, (2, H, W)).astype(np.int32)
    return _CONFIGS[0], _CONFIGS[1], jnp.asarray(canvas)


@st.composite
def mesh_cases(draw):
    """Random (H, W, radius, app, rows, seed), capped to the host's
    device budget; covers rows not dividing H, H < rows bands, and
    radius-0 layouts by construction of the ranges."""
    H = draw(st.integers(2, 20))
    W = draw(st.integers(2, 20))
    radius = draw(st.integers(1, 2))
    app = draw(st.integers(1, 2))
    rows = draw(st.integers(1, max(1, N_DEVICES // app)))
    seed = draw(st.integers(0, 2**31 - 1))
    return H, W, radius, app, rows, seed


@needs_two_devices
@settings(max_examples=15, deadline=None)
@given(mesh_cases())
def test_property_2d_parity(case):
    H, W, radius, app, rows, seed = case
    stacked, ingests, canvas = _workload(H, W, seed)
    outs = []
    for spec in (MeshSpec(), MeshSpec(app=app, rows=rows)):
        plan = OverlayPlan(grid=GRID, batched=True, fused=True,
                           radius=radius, mesh=spec)
        outs.append(np.asarray(compile_plan(plan)(stacked, ingests, None,
                                                  canvas)))
    np.testing.assert_array_equal(outs[0], outs[1])
