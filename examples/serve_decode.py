"""Batched serving driver (the paper-kind end-to-end example: the paper is
an inference accelerator, so the e2e driver serves a model with batched
requests through the slot-based continuous-batching loop).

  PYTHONPATH=src python examples/serve_decode.py [--arch granite_3_2b]
"""

import argparse
import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_smoke_config
from repro.models.transformer import count_params, init_params
from repro.runtime.serve import ServeConfig, ServeLoop
from repro.serve import Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_3_2b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--new-tokens", type=int, default=16)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config(args.arch)
    params, _, statics = init_params(cfg, jax.random.PRNGKey(0))
    print(f"{cfg.name}: {count_params(params):,} params")

    scfg = ServeConfig(
        batch_slots=args.slots,
        max_seq=args.prompt_len + args.new_tokens + 8,
        eos_id=-1,
    )
    loop = ServeLoop(cfg, statics, params, scfg)

    rng = np.random.default_rng(0)
    reqs = [
        Request(
            prompt=rng.integers(1, cfg.vocab, args.prompt_len).astype(np.int32),
            max_new_tokens=args.new_tokens,
        )
        for _ in range(args.requests)
    ]
    t0 = time.time()
    loop.generate(reqs)
    dt = time.time() - t0
    total = sum(len(r.output) for r in reqs)
    print(f"{len(reqs)} requests x {args.new_tokens} tokens "
          f"({args.slots} slots): {total} tokens in {dt:.2f}s "
          f"= {total/dt:.1f} tok/s")
    for i, r in enumerate(reqs[:3]):
        print(f"request {i}: {r.output[:10]}...")


if __name__ == "__main__":
    main()
