"""The benchmark's own CPU tests, outside tier-1's ``testpaths``:

    JAX_PLATFORMS=cpu python -m pytest -q -p no:cacheprovider perfbench/tests

They put the benchmark's directory and the checkout's ``src`` on the
path, as ``perfbench/run.py`` does for itself."""

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(HERE.parent), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
