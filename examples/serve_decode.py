"""Batched serving example: prefill + slot-based continuous batching over a
registered architecture (greedy decode).

    PYTHONPATH=src python examples/serve_decode.py --arch yi-6b --requests 6
"""
import argparse
import time

import jax
import numpy as np

from repro.compile_cache import setup_compile_cache
from repro.models.registry import get_config, get_model
from repro.serve.engine import Request, ServeEngine


def main() -> None:
    setup_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=12)
    ap.add_argument("--slots", type=int, default=3)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    eng = ServeEngine(api, max_len=args.prompt_len + args.new_tokens + 8,
                      batch_slots=args.slots)
    eng.load(params)

    rng = np.random.default_rng(0)
    reqs = [
        Request(prompt=rng.integers(0, cfg.vocab, args.prompt_len,
                                    dtype=np.int32),
                max_new_tokens=args.new_tokens)
        for _ in range(args.requests)
    ]
    t0 = time.time()
    eng.run(reqs)
    dt = time.time() - t0
    total = sum(len(r.out) for r in reqs)
    print(f"arch={args.arch} (reduced) — {len(reqs)} requests, "
          f"{total} tokens in {dt:.2f}s ({total / dt:.1f} tok/s on CPU)")
    for i, r in enumerate(reqs):
        print(f"  req{i}: {r.out}")


if __name__ == "__main__":
    main()
