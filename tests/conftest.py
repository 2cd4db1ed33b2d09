# NOTE: do NOT set XLA_FLAGS / host-device-count here -- smoke tests and
# benches must see the single real CPU device; only launch/dryrun.py forces
# 512 placeholder devices (and does so before any jax import).
import os

import numpy as np
import pytest

os.environ.setdefault("JAX_ENABLE_X64", "0")

# Test tiers (registered in pyproject.toml [tool.pytest.ini_options]):
#   tier-1 (CI gate, < 5 min):  pytest            (addopts apply -m "not slow")
#   full / nightly:             pytest -m ""      (marker filter disabled)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def shared_app_grid(app_names, name="shared", slack=1):
    """One grid big enough for every named library app (the paper's
    "application specific grid designs", Sec. III-C): per-level width =
    max demand across the apps + slack.  Shared by the fleet/ingest/
    property suites so multi-tenant tests stack different apps on one
    overlay.  (Imports deferred: see the jax note at the top.)"""
    from repro.core import applications as apps
    from repro.core.grid import custom
    from repro.core.place import level_demand

    dfgs = [apps.ALL_APPS[n]() for n in app_names]
    demands = [level_demand(g) for g in dfgs]
    depth = max(len(d) for d in demands)
    demands = [list(d) + [1] * (depth - len(d)) for d in demands]
    widths = [max(d[lvl] for d in demands) + slack for lvl in range(depth)]
    return custom(name, max(len(g.inputs) for g in dfgs), widths, 1)
