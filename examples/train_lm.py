"""End-to-end training driver: a ~100M-parameter gemma-family model for a
few hundred steps on CPU, with checkpoint/restart fault tolerance.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]

The same `repro.train` stack drives full-size archs over the production
mesh (see repro/launch/train.py and the dry-run).
"""

import argparse
import dataclasses
import shutil
import tempfile

import jax

from repro.compile_cache import enable_compile_cache
from repro.configs import get_arch
from repro.data import TokenPipeline
from repro.models import LM
from repro.optim import AdamWConfig
from repro.train import LoopConfig, train_loop


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=256)
    args = ap.parse_args()

    # ~100M params: gemma-family, 8 layers, d=768, vocab 32768
    base = get_arch("gemma-2b")
    cfg = dataclasses.replace(
        base, name="gemma-100m", num_layers=8, d_model=768, num_heads=8,
        num_kv_heads=1, head_dim=96, d_ff=3072, vocab_size=32_768,
    )
    lm = LM(cfg, remat="none", chunk_q=128, loss_chunk=128)
    n_params = sum(
        x.size for x in jax.tree_util.tree_leaves(
            jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
        )
    )
    print(f"model: {cfg.name}  params={n_params/1e6:.1f}M")

    cycle = max(1, min(16, args.steps // 4))

    class CyclingPipeline(TokenPipeline):
        """Cycle over a fixed batch set so the demo has learnable signal
        (the raw hash stream is uniform => CE would flatline at ln V)."""

        def batch_at(self, step):
            return super().batch_at(step % cycle)

    pipeline = CyclingPipeline(
        vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch
    )
    ckpt_dir = tempfile.mkdtemp(prefix="pixie_train_")
    try:
        hist = train_loop(
            lm,
            LoopConfig(steps=args.steps, ckpt_every=100, ckpt_dir=ckpt_dir,
                       log_every=20),
            AdamWConfig(lr=3e-4, warmup_steps=30, total_steps=args.steps),
            pipeline,
        )
        first, last = hist["loss"][0], hist["loss"][-1]
        print(f"\nloss: {first:.3f} -> {last:.3f} over {args.steps} steps "
              f"({hist['throughput_tok_s'][0]:,.0f} tok/s median)")
        assert last < first, "training did not reduce the loss"
        print("training reduced the loss  [ok]")
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)


if __name__ == "__main__":
    main()
