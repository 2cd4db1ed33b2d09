"""Compile the served megakernels for a described TPU v5e, without a chip.

Interpret mode (every other kernel suite) accepts programs the TPU
compiler refuses: unaligned block shapes, value-level dynamic slices,
more VMEM than one kernel may use.  These tests lower and compile the
main-path kernels with ``interpret=False`` against a described
``v5e:2x2`` topology at real canvas widths (1080p frames land on a
2048^2 canvas, 4K frames on 4096^2) with the AUTO tile height, and on
the small canvases the fleet buckets small frames into, so a change the
chip would refuse fails here.  Nothing runs: results are
checked bitwise on the chip by ``chip_smoke.py``.  One more lowering
checks that a kernel's persistent-cache key names no checkout path.

The topology is described inside a module fixture (never at import
time): only one process may load the TPU library, and describing it
loads it.  Keep every such compile in this one file.
"""

import base64
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.compile_cache import CHECKOUT, SOURCE_PREFIX_REGEX
from repro.core import applications, map_app, sobel_grid
from repro.core.plan import OverlayPlan, PipelineSpec, compile_plan
from repro.core.tiling import TILE_AUTO
from repro.kernels.vcgra import vcgra_kernel
from repro.kernels.vcgra.ops import (
    _batched_fused_pallas_fn,
    _batched_pallas_fn,
    pallas_pipeline_fn,
)

GRID = sobel_grid()
N = 8
#: A described v5e's HBM, which every compiled program must fit.
V5E_HBM_BYTES = 16 * 1000**3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described device is written to the persistent cache
    # but cannot be read back without the device: keep the cache off.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler here: skip
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _configs(sharding, grid=GRID, n=N):
    return (
        tuple(_spec(sharding, (n, w)) for w in grid.pes_per_level),
        tuple(_spec(sharding, (n, w, 2)) for w in grid.pes_per_level),
        _spec(sharding, (n, grid.num_outputs)),
    )


def _ingests(sharding, grid=GRID, n=N):
    return (_spec(sharding, (n, grid.num_inputs)),
            _spec(sharding, (n, grid.num_inputs), grid.dtype))


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < V5E_HBM_BYTES
    return compiled


@pytest.mark.parametrize("side", [2048, 4096])
def test_fused_megakernel_compiles(one_chip, side):
    fn = _batched_fused_pallas_fn(GRID, 1, interpret=False,
                                  tile_rows=TILE_AUTO)
    _compile(fn, _configs(one_chip), _ingests(one_chip),
             _spec(one_chip, (N, 2)),
             _spec(one_chip, (N, side, side), GRID.dtype))


def test_pipeline_megakernel_compiles(one_chip):
    radii = (1, 1, 0)
    fn = pallas_pipeline_fn(GRID, radii, tile_rows=TILE_AUTO, interpret=False)
    stages = tuple((_configs(one_chip), _ingests(one_chip),
                    _spec(one_chip, (N,))) for _ in radii)
    _compile(fn, stages, _spec(one_chip, (N, 2)),
             _spec(one_chip, (N, 2048, 2048), GRID.dtype))


@pytest.mark.parametrize("hw,tile_rows", [
    ((16, 32), None),          # width padded to one lane block
    ((64, 128), 8),            # 128-lane blocks, 8 tiles
    ((128, 256), TILE_AUTO),   # 256-lane blocks
    ((200, 300), 16),          # width padded to 384: 128-lane blocks
])
def test_small_canvas_fused_megakernel_compiles(one_chip, hw, tile_rows):
    """The canvases small frames land on: lane padding and each block
    width the datapath walks."""
    fn = _batched_fused_pallas_fn(GRID, 1, interpret=False,
                                  tile_rows=tile_rows)
    _compile(fn, _configs(one_chip, n=2), _ingests(one_chip, n=2),
             _spec(one_chip, (2, 2)), _spec(one_chip, (2, *hw), GRID.dtype))


def test_float_grid_fused_megakernel_compiles(one_chip):
    """A float32 grid: every PE unit branch, the float DIV among them,
    compiles for the chip."""
    grid = sobel_grid(float_pe=True)
    fn = _batched_fused_pallas_fn(grid, 1, interpret=False, tile_rows=8)
    _compile(fn, _configs(one_chip, grid, n=2),
             _ingests(one_chip, grid, n=2), _spec(one_chip, (2, 2)),
             _spec(one_chip, (2, 64, 128), grid.dtype))


def test_small_canvas_pipeline_megakernel_compiles(one_chip):
    """A chain whose rounded trapezoid rows exceed the tile on a padded
    canvas."""
    radii = (1, 2, 0)
    fn = pallas_pipeline_fn(GRID, radii, tile_rows=8, interpret=False)
    stages = tuple((_configs(one_chip, n=2), _ingests(one_chip, n=2),
                    _spec(one_chip, (2,))) for _ in radii)
    _compile(fn, stages, _spec(one_chip, (2, 2)),
             _spec(one_chip, (2, 27, 200), GRID.dtype))


def test_channel_kernel_compiles(one_chip):
    fn = _batched_pallas_fn(GRID, interpret=False)
    compiled = _compile(fn, _configs(one_chip),
                        _spec(one_chip, (N, GRID.num_inputs, 2048 * 2048),
                              GRID.dtype))
    assert "%vcgra_batched" in compiled.as_text()


@pytest.mark.parametrize("kind", ["fused", "pipeline"])
def test_served_plans_name_their_megakernels(one_chip, monkeypatch, kind):
    """The fleet's async pallas plans compile to a module and a kernel
    that a device trace tells apart -- ``jit_pixie_<kind>_dispatch``
    around ``vcgra_<kind>_batched`` -- and the kernel is still a
    ``tpu_custom_call``, the string the roofline readers match."""
    monkeypatch.setattr(vcgra_kernel, "default_interpret", lambda: False)
    n = 2
    frames = _spec(one_chip, (n, 256, 256), GRID.dtype)
    if kind == "fused":
        plan = OverlayPlan(grid=GRID, batched=True, fused=True, radius=1,
                           backend="pallas", tile_rows=TILE_AUTO,
                           ingest="async")
        args = (_configs(one_chip, n=n), _ingests(one_chip, n=n),
                _spec(one_chip, (n, 2)), frames)
    else:
        spec = PipelineSpec.chain([
            map_app(applications.ALL_APPS[a](), GRID)
            for a in ("sobel_x", "threshold")])
        plan = OverlayPlan(grid=GRID, batched=True, pipeline=(spec,) * n,
                           backend="pallas", tile_rows=TILE_AUTO,
                           ingest="async")
        args = (tuple((_configs(one_chip, n=n), _ingests(one_chip, n=n),
                       _spec(one_chip, (n,))) for _ in spec.radii),
                _spec(one_chip, (n, 2)), frames)
    text = compile_plan(plan).lower(*args).compile().as_text()
    assert f"HloModule jit_pixie_{kind}_dispatch," in text
    kernels = re.findall(r"%(vcgra_\w+)[.\d]* = (.*)", text)
    assert [name for name, _ in kernels] == [f"vcgra_{kind}_batched"]
    assert 'custom_call_target="tpu_custom_call"' in kernels[0][1]


def _mosaic_payload(sharding, source_regex):
    """The serialized Mosaic module of the fused kernel, lowered with
    ``source_regex`` as the source-file canonicalization rule."""
    was = jax.config.jax_hlo_source_file_canonicalization_regex
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      source_regex)
    try:
        fn = _batched_fused_pallas_fn(GRID, 1, interpret=False,
                                      tile_rows=TILE_AUTO)
        text = jax.jit(fn).lower(
            _configs(sharding), _ingests(sharding), _spec(sharding, (N, 2)),
            _spec(sharding, (N, 256, 256), GRID.dtype)).as_text()
    finally:
        jax.config.update("jax_hlo_source_file_canonicalization_regex", was)
    body = re.search(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text)
    return base64.b64decode(body.group(1))


def test_kernel_cache_key_names_no_checkout_path(one_chip):
    """JAX hashes a kernel's Mosaic payload with its source locations into
    the persistent-cache key; under the entry points' rule those name no
    absolute checkout path, so another checkout of the same code hits."""
    checkout = str(CHECKOUT).encode()
    assert checkout in _mosaic_payload(one_chip, None)
    payload = _mosaic_payload(one_chip, SOURCE_PREFIX_REGEX)
    assert b"src/repro/kernels/vcgra/vcgra_kernel.py" in payload
    assert checkout not in payload
