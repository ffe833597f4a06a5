"""Serving launcher: ``python -m repro.launch.serve --arch <id>``."""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, reduced
from ..models import init_params
from ..serve.engine import Request, ServeEngine
from .compile_cache import enable_compile_cache


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--ctx", type=int, default=128)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = reduced(get_config(args.arch))
    if not cfg.causal:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode serving")
    params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    engine = ServeEngine(cfg, params, batch_slots=args.slots,
                         ctx_len=args.ctx)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i,
                    prompt=rng.integers(0, cfg.vocab, args.prompt_len,
                                        dtype=np.int32),
                    max_new_tokens=args.max_new)
            for i in range(args.requests)]
    stats = engine.run(reqs)
    for r in reqs:
        assert r.done and len(r.out_tokens) == args.max_new
    print(f"arch={cfg.name} requests={len(reqs)} "
          f"decode_steps={stats.decode_steps} "
          f"tokens={stats.tokens_out} tok/s={stats.tokens_per_s:.1f}")


if __name__ == "__main__":
    main()
