"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
call :func:`enable_compile_cache` once, before their first compile, so a
second run of the same program on the same machine reloads its compiled
executables -- the 1080p megakernels included -- instead of compiling
them again.  Tests never call it: a test run keeps JAX's defaults.

The place is decided outside the program.  When ``JAX_COMPILATION_CACHE_DIR``
is set, JAX already reads it and nothing is set here.  Otherwise the cache
lives at a fixed in-checkout path, ``<checkout>/.jax_cache`` (listed in
``.gitignore``): fixed, because the directory is part of what a later run
looks up, so a name that changed per run would never hit.

The key must not depend on where the checkout lives either.  A Pallas
TPU kernel is embedded in its program as a serialized Mosaic module that
keeps its source locations, and JAX hashes that payload as it is (it
strips locations only from the outer program), so every pallas
executable's key would carry the checkout's absolute path and a second
checkout of the same commit would compile every kernel again.  The
checkout prefix is therefore cut from source file names at lowering
(``jax_hlo_source_file_canonicalization_regex``), unless the environment
already chose a rule.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[2]

#: The in-checkout cache directory used when the environment names none.
DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"

#: Removed from every source file name JAX writes into a lowered program:
#: the checkout's absolute prefix, so ``<checkout>/src/repro/...`` reads
#: ``src/repro/...`` whatever directory holds the checkout.
SOURCE_PREFIX_REGEX = "^" + re.escape(str(CHECKOUT) + os.sep)


def enable_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory."""
    if jax.config.jax_hlo_source_file_canonicalization_regex is None:
        jax.config.update("jax_hlo_source_file_canonicalization_regex",
                          SOURCE_PREFIX_REGEX)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
