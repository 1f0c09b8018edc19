"""Train an assigned-architecture LM end to end on synthetic data.

Default: reduced yi-6b (~0.5M params) for 200 steps on CPU; any --arch works.
For the "~100M params for a few hundred steps" configuration (TPU-scale
budget), pass --d-model 512 --layers 24 --steps 300 — same code path.

    PYTHONPATH=src python examples/train_lm.py --arch rwkv6-3b --steps 120
"""
from __future__ import annotations

import argparse

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import train


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default=None)
    args = ap.parse_args()
    hist = train(args.arch, smoke=True, steps=args.steps, batch=args.batch,
                 seq=args.seq, ckpt=args.ckpt)
    assert hist["loss"][-1] < hist["loss"][0], "training did not reduce loss"
    print(f"OK: loss {hist['loss'][0]:.3f} -> {hist['loss'][-1]:.3f}")


if __name__ == "__main__":
    main()
