"""Training CLI.

Container scale: reduced configs train for real on CPU (synthetic/Markov
data) with the full fault-tolerance path (checkpoint/restart, straggler
monitor). Production scale: pass --production to build the 16×16(-per-pod)
mesh and shard params/opt/data with the framework rules.

Examples:
    python -m repro.launch.train --arch granite-3-8b --reduced --steps 200
    python -m repro.launch.train --arch mixtral-8x7b --reduced --steps 100 \
        --pod-compress   # int8 cross-pod gradient all-reduce (needs pods)
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro.configs import get_config, list_archs
from repro.data.synthetic import markov_batches, synthetic_batches
from repro.models.model import build_model
from repro.training.optimizer import AdamWConfig
from repro.training.train_loop import Trainer, TrainerConfig, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-8b", choices=list_archs())
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--data", default="markov", choices=["markov", "random"])
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--pod-compress", action="store_true")
    ap.add_argument("--production", action="store_true",
                    help="build the production mesh (needs ≥256 devices)")
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced=args.reduced)
    if args.reduced:
        cfg = dataclasses.replace(cfg, dtype="float32")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    mesh = None
    if args.production:
        from repro.distributed import compat
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh(multi_pod=args.pod_compress)
        compat.activate_mesh(mesh)

    ocfg = AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps // 5),
                       decay_steps=args.steps)
    step = jax.jit(make_train_step(model, ocfg,
                                   microbatches=args.microbatches,
                                   pod_compress=args.pod_compress,
                                   mesh=mesh))
    trainer = Trainer(model, ocfg,
                      TrainerConfig(total_steps=args.steps,
                                    checkpoint_every=args.ckpt_every,
                                    checkpoint_dir=args.ckpt_dir),
                      train_step=step)
    gen = markov_batches if args.data == "markov" else synthetic_batches
    extra = {}
    if cfg.is_encdec:
        extra = {"encdec_dim": cfg.d_model, "enc_ratio": cfg.enc_ratio}
    it = (jax.tree_util.tree_map(jnp.asarray, b)
          for b in gen(args.batch, args.seq, cfg.vocab, seed=0, **extra))
    params, opt, info = trainer.run(params, it)
    hist = info["history"]
    print(f"[train] done: loss {hist[0]:.4f} → {hist[-1]:.4f} "
          f"({len(hist)} steps, {len(info['stragglers'])} stragglers)")


if __name__ == "__main__":
    main()
