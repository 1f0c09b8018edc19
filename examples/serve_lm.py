"""Serve a (reduced) assigned architecture: batched prefill + decode loop.

    PYTHONPATH=src python examples/serve_lm.py --arch rwkv6-3b --gen 48
"""
from __future__ import annotations

import argparse

from repro.launch.compile_cache import enable_compile_cache
from repro.launch.serve import serve


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="yi-6b")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=48)
    args = ap.parse_args()
    stats = serve(args.arch, smoke=True, batch=args.batch,
                  prompt_len=args.prompt_len, gen=args.gen)
    assert stats["decode_tok_per_s"] > 0


if __name__ == "__main__":
    main()
