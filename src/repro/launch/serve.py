"""Serving launcher.

Local mode boots the engine on this host's devices with the architecture's
published config (``--smoke`` for its reduced toy) over seeded random
weights and serves a batch of synthetic requests; ``--dry-run`` lowers the
full-config prefill/decode steps for the production mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch starcoder2-3b --requests 8
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --smoke
    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-32b --dry-run
"""

from __future__ import annotations

import argparse
import sys
import time
from typing import List

import numpy as np


def build_engine(cfg, *, batch: int, max_len: int, seed: int = 0):
    """Engine over random weights drawn from ``seed`` on the default device."""
    import jax

    from repro.models import params as P
    from repro.serve.engine import Engine

    if cfg.input_mode != "tokens":
        raise ValueError(
            f"{cfg.name} takes input_mode={cfg.input_mode!r}; the engine serves token LMs")
    params = P.init_params(cfg, jax.random.key(seed))
    return Engine(cfg, params, batch=batch, max_len=max_len, seed=seed)


def make_requests(cfg, n: int, *, prompt_len: int, max_new_tokens: int,
                  seed: int = 0) -> List:
    """``n`` greedy requests with seeded random prompts of ``prompt_len``."""
    from repro.serve.engine import Request

    rng = np.random.default_rng(seed)
    return [
        Request(uid=i,
                prompt=rng.integers(1, cfg.vocab_size, size=prompt_len).astype(np.int32),
                max_new_tokens=max_new_tokens)
        for i in range(n)
    ]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="serve the architecture's reduced toy config")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args(argv)

    if args.dry_run:
        from repro.launch import dryrun

        ok = True
        for shape in ("prefill_32k", "decode_32k"):
            rec = dryrun.run_cell(args.arch, shape, multi_pod=args.multi_pod)
            ok = ok and rec.get("status") in ("ok", "skipped")
        return 0 if ok else 1

    from repro import configs

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get_config(args.arch)
    try:
        eng = build_engine(cfg, batch=args.batch, max_len=args.max_len)
    except ValueError as e:
        ap.error(str(e))
    reqs = make_requests(cfg, args.requests, prompt_len=args.prompt_len,
                         max_new_tokens=args.max_new_tokens)
    t0 = time.perf_counter()
    outs = eng.generate(reqs)
    dt = time.perf_counter() - t0
    total = sum(len(c.tokens) for c in outs)
    print(f"{len(reqs)} requests, {total} tokens, {dt:.2f}s -> {total/dt:.1f} tok/s")
    return 0


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
