"""End-to-end LM training driver with the full production runtime:
packed data pipeline, AdamW + cosine schedule, fault-tolerant Trainer
(async checkpoints, resume), optional pattern-sparse MLPs.

  PYTHONPATH=src python examples/train_lm.py --steps 200
  PYTHONPATH=src python examples/train_lm.py --hundred-m --steps 300  # ~100M

The default config (~10M params) trains a few hundred steps in CPU-minutes;
--hundred-m selects a ~100M-param model for real hardware.
"""

import argparse

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.data.pipeline import DataConfig, packed_batches
from repro.models.transformer import ModelConfig, count_params, init_params
from repro.optim import adamw, linear_warmup_cosine
from repro.runtime.train import (
    TrainConfig,
    Trainer,
    init_train_state,
    make_train_step,
)


def small_config(hundred_m: bool) -> ModelConfig:
    if hundred_m:
        return ModelConfig(
            name="lm100m", n_layers=12, d_model=768, vocab=32000,
            layer_types=(("attn", "mlp"),) * 12, n_heads=12, n_kv_heads=4,
            d_head=64, d_ff=2048, model_shards=1, max_seq=1024,
        )
    return ModelConfig(
        name="lm10m", n_layers=4, d_model=256, vocab=2048,
        layer_types=(("attn", "mlp"),) * 4, n_heads=8, n_kv_heads=4,
        d_head=32, d_ff=768, model_shards=1, max_seq=512,
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--hundred-m", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = small_config(args.hundred_m)
    params, _, statics = init_params(cfg, jax.random.PRNGKey(0))
    print(f"{cfg.name}: {count_params(params)/1e6:.1f}M params")

    opt = adamw()
    tcfg = TrainConfig(
        steps=args.steps, ckpt_every=max(args.steps // 4, 1),
        ckpt_dir=args.ckpt_dir, async_ckpt=True,
    )
    lr_fn = linear_warmup_cosine(args.lr, 20, args.steps)
    step = jax.jit(make_train_step(cfg, statics, opt, lr_fn, tcfg),
                   donate_argnums=(0,))
    state = init_train_state(params, opt, tcfg)

    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch)
    trainer = Trainer(step, state, packed_batches(dcfg), tcfg)
    resumed = trainer.maybe_restore()
    if resumed:
        print(f"resumed from checkpoint at step {resumed}")
    hist = trainer.run()
    for h in hist[:: max(1, len(hist) // 15)]:
        print(f"step {h['step']:5d} loss {h['loss']:.4f} "
              f"({h['seconds']*1e3:.0f} ms/step)")
    print(f"final loss {hist[-1]['loss']:.4f}  "
          f"stragglers flagged: {len(trainer.straggler.flagged)}")


if __name__ == "__main__":
    main()
