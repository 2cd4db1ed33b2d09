"""Quickstart: the paper's demonstrator end to end.

Builds the 45-PE/4-VC Sobel grid (paper Fig. 5), runs the full VCGRA tool
flow (synthesis -> place -> route -> settings), executes on both the
compile-once conventional overlay and the parameterized (specialized)
path, validates against the numpy convolution oracle, and shows the
compile-gap numbers the paper is about.

    PYTHONPATH=src python examples/quickstart.py
"""

import time

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.core import Pixie, SOBEL_SOURCE, map_app, sobel_grid, synthesize, for_dfg
from repro.core import applications as apps
from repro.core.grid import rectangular
from repro.core.place import level_demand


def main():
    enable_compile_cache()
    print("=== Pixie quickstart: Sobel on the 45-PE VCGRA (paper Sec. IV) ===\n")

    # 1. the application, synthesized from its textual description
    dfg = synthesize("sobel_mag", SOBEL_SOURCE)
    print(f"synthesized netlist: {dfg.num_ops()} PE ops, depth {dfg.depth()}, "
          f"{len(dfg.inputs)} memory inputs")

    # 2. the overlay grid + tool flow (map < 1 s is the paper's headline).
    #    Size the grid to host every app we'll reconfigure onto it.
    blur_dfg = apps.gaussian_blur()
    d1, d2 = level_demand(dfg), level_demand(blur_dfg)
    grid = rectangular(
        "demo",
        num_inputs=max(len(dfg.inputs), len(blur_dfg.inputs)),
        levels=max(len(d1), len(d2)),
        width=max(max(d1), max(d2)),
        num_outputs=1,
    )
    pix = Pixie(grid, mode="conventional")
    t0 = time.perf_counter()
    config = pix.map(dfg)
    print(f"map (synth+place+route+settings): {1e3*(time.perf_counter()-t0):.1f} ms "
          f"(paper: < 1 s)")
    print(f"settings: {config.settings_words()} words "
          f"({config.settings_bits(grid)} bits)")

    # 3. compile the overlay ONCE (the '1200 s FPGA compile' analogue)
    img = jnp.asarray(np.random.default_rng(0).integers(0, 256, (256, 256)).astype(np.int32))
    t = pix.compile_overlay(batch=img.size)
    print(f"overlay compile (once per grid): {t:.2f} s")

    # 4. load + run, check against the oracle
    pix.load(config)
    out = np.asarray(pix.run_image(img))
    ref = apps.sobel_magnitude_reference(np.asarray(img))
    assert np.array_equal(out, ref), "overlay output mismatch!"
    print("conventional overlay == numpy oracle  [ok]")

    # 5. reconfigure to a different app WITHOUT recompiling
    blur = pix.map(blur_dfg)
    t_sw = pix.load(blur)
    out2 = np.asarray(pix.run_image(img))
    ref2 = apps.conv2d_reference(np.asarray(img), apps.GAUSS3, divisor=16.0)
    assert np.array_equal(out2, ref2)
    print(f"reconfigured to gauss3 in {1e3*t_sw:.2f} ms (settings swap, no re-jit)  [ok]")

    # 6. the parameterized path (paper's TLUT/TCON optimization)
    pixp = Pixie(grid, mode="parameterized")
    t_r = pixp.load(config, batch=img.size)
    out3 = np.asarray(pixp.run_image(img))
    assert np.array_equal(out3, ref)
    print(f"parameterized (specialized) path: micro-reconfig {t_r:.2f} s, "
          f"output identical  [ok]")

    print("\nquickstart complete.")


if __name__ == "__main__":
    main()
