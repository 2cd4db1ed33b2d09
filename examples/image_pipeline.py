"""The Pixie overlay as a first-class data-pipeline feature.

A VLM preprocessing pipeline where the image filter bank runs on the
compiled-once VCGRA overlay: switching augmentation/filter policy is a
settings swap (never a recompile), exactly the overlay's value
proposition transplanted into a production data path.  The filtered
images feed the SigLIP-stub patch embedder used by the paligemma-3b
config.

    PYTHONPATH=src python examples/image_pipeline.py
"""

import time

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch
from repro.data import PixiePreprocessor, patch_embed_stub, synthetic_images


def main():
    enable_compile_cache()
    cfg = get_arch("paligemma-3b")
    pre = PixiePreprocessor(filters=("sobel_mag", "gauss3", "sharpen", "laplace"))
    print(f"overlay grid: {pre.grid}")

    images = synthetic_images(8, (64, 64))
    t0 = time.perf_counter()
    feats = {}
    for name in pre.filters:
        pre.reconfigure(name)           # settings swap, no re-jit
        feats[name] = np.asarray(pre.batch(jnp.asarray(images)))
    dt = time.perf_counter() - t0
    print(f"4 filter policies x 8 images through one overlay executable "
          f"in {dt:.2f}s (cache size {pre.overlay._cache_size()} executable)")

    # stub patch embeddings for the VLM (dry-run feeds these shapes)
    emb = patch_embed_stub(feats["sobel_mag"], cfg.prefix_tokens, cfg.d_model)
    print(f"patch embeddings for {cfg.name}: {emb.shape} "
          f"(prefix_tokens={cfg.prefix_tokens}, d_model={cfg.d_model})")
    assert emb.shape == (8, cfg.prefix_tokens, cfg.d_model)
    print("pipeline complete  [ok]")


if __name__ == "__main__":
    main()
