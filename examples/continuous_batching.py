"""Serve a stream of requests through the continuous-batching scheduler.

    PYTHONPATH=src python examples/continuous_batching.py --arch rwkv6-3b
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_model_config
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.scheduler import ContinuousBatcher, Request
from repro.models import transformer as T


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    args = ap.parse_args()

    cfg = get_model_config(args.arch, smoke=True)
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    batcher = ContinuousBatcher(cfg, params, batch_slots=args.slots,
                                max_len=128)
    rng = np.random.default_rng(0)
    for i in range(args.requests):
        plen = int(rng.integers(4, 16))
        batcher.submit(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
            max_new=args.max_new))
    stats = batcher.run()
    print(f"arch={cfg.name} slots={args.slots} requests={args.requests}")
    print(f"completed={stats.completed} decode_steps={stats.decode_steps} "
          f"tokens={stats.tokens_out}")
    print(f"throughput={stats.tok_per_s:,.1f} tok/s  "
          f"mean TTFT={stats.mean_ttft_s * 1e3:.0f} ms  "
          f"mean latency={stats.mean_latency_s * 1e3:.0f} ms")
    for r in batcher.completed[:3]:
        print(f"  req {r.rid}: {r.out[:10]}")


if __name__ == "__main__":
    main()
