"""Training launcher (the Jacamar-runner analogue).

Local execution trains the selected architecture's smoke/custom config on
this host's devices with checkpoint/restart; ``--dry-run`` lowers the FULL
config against the production mesh instead (use ``repro.launch.dryrun``
directly for the full matrix).

    PYTHONPATH=src python -m repro.launch.train --arch glm4-9b --steps 50
    PYTHONPATH=src python -m repro.launch.train --arch glm4-9b --dry-run
"""

from __future__ import annotations

import argparse
import dataclasses
import sys


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--opt-state", default="float32", choices=["float32", "q8"])
    ap.add_argument("--stochastic-rounding", action="store_true")
    ap.add_argument("--dry-run", action="store_true",
                    help="lower the FULL config on the production mesh instead")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args(argv)

    if args.dry_run:
        from repro.launch import dryrun

        rec = dryrun.run_cell(args.arch, "train_4k", multi_pod=args.multi_pod,
                              opt_state_dtype=args.opt_state, microbatches=8)
        return 0 if rec.get("status") == "ok" else 1

    from repro import configs
    from repro.checkpoint.checkpoint import CheckpointManager
    from repro.data.pipeline import DataConfig
    from repro.train import optimizer as O
    from repro.train.trainer import TrainConfig, train

    cfg = configs.get_smoke(args.arch)
    tc = TrainConfig(
        steps=args.steps,
        data=DataConfig(seq_len=args.seq, global_batch=args.batch),
        opt=O.OptConfig(
            lr=args.lr, total_steps=args.steps, warmup_steps=max(args.steps // 10, 1),
            state_dtype=args.opt_state, stochastic_rounding=args.stochastic_rounding,
        ),
        remat="none",
    )
    ckpt = CheckpointManager(args.ckpt) if args.ckpt else None
    res = train(cfg, tc, ckpt=ckpt,
                on_step=lambda s, m: print(f"step {s}: loss={m['loss']:.4f}")
                if s % 10 == 0 else None)
    print(f"final loss: {res.final_loss:.4f}")
    return 0


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main())
