"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On this container it runs reduced configs on the local device; on a real
cluster the same driver runs the full config with the production mesh
(--mesh production) — the step function, sharding rules, checkpointing and
data pipeline are identical code paths.
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from ..configs import get_config, reduced
from ..train.data import DataConfig
from ..train.optimizer import AdamW
from ..train.train_loop import TrainConfig, train
from .compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--full-config", action="store_true",
                    help="use the full architecture (cluster-scale only)")
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = reduced(cfg, n_layers=args.layers, d_model=args.d_model,
                      d_ff=4 * args.d_model, vocab=512)
    data_cfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                          global_batch=args.batch,
                          frontend_dim=cfg.frontend_dim
                          if cfg.frontend != "none" else 0)
    tc = TrainConfig(steps=args.steps, ckpt_dir=args.ckpt_dir,
                     log_every=10)
    opt = AdamW(lr=args.lr)
    params, opt_state, report = train(cfg, data_cfg, tc, opt=opt)
    print(f"arch={cfg.name} steps={len(report.losses)} "
          f"first_loss={report.losses[0]:.4f} "
          f"final_loss={report.final_loss:.4f} "
          f"resumed_from={report.resumed_from} "
          f"stragglers={len(report.straggler_steps)}")


if __name__ == "__main__":
    main()
