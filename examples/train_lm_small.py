"""Train a small (~25M param) LM with the full production stack: microbatch
gradient accumulation, AdamW + cosine schedule, async checkpointing and
crash-safe resume. A second invocation resumes from the latest checkpoint.

  PYTHONPATH=src python examples/train_lm_small.py [--steps 200]
"""
import argparse

import jax
import jax.numpy as jnp

from repro.configs.base import LMConfig
from repro.models.transformer import init_lm
from repro.train.optimizer import adamw, cosine_schedule
from repro.train.train_step import TrainState, make_lm_train_step
from repro.train.trainer import Trainer, TrainerConfig
from repro.launch.compile_cache import enable_compile_cache


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm_ckpt")
    args = ap.parse_args(argv)

    cfg = LMConfig(name="lm-25m", n_layers=6, d_model=384, n_heads=6,
                   n_kv_heads=2, d_head=64, d_ff=1024, vocab=8192)
    print(f"params: {cfg.param_count()/1e6:.1f}M")

    params = init_lm(jax.random.key(0), cfg)
    opt = adamw(cosine_schedule(3e-4, 20, args.steps))
    state = TrainState(params=params, opt=opt.init(params))
    step = jax.jit(make_lm_train_step(cfg, opt, num_microbatches=2))

    def batch_fn(i):
        key = jax.random.fold_in(jax.random.key(42), i)
        toks = jax.random.randint(key, (8, 128), 0, cfg.vocab)
        return {"tokens": toks, "targets": jnp.roll(toks, -1, axis=1)}

    trainer = Trainer(step, batch_fn, state,
                      TrainerConfig(total_steps=args.steps, ckpt_every=50,
                                    ckpt_dir=args.ckpt_dir, log_every=20))
    trainer.maybe_restore()
    trainer.run()
    print("done; metrics tail:", trainer.metrics_log[-2:])


if __name__ == "__main__":
    enable_compile_cache()
    main()
