import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

Lowers + compiles every (architecture × input-shape) cell against the
production meshes — 16×16 single-pod and 2×16×16 multi-pod — on 512
placeholder host devices, prints ``memory_analysis``/``cost_analysis``, and
derives the roofline terms from the compiled artifact via the loop-aware
HLO cost model.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b --shape train_4k --multi-pod
    PYTHONPATH=src python -m repro.launch.dryrun --all --out results/dryrun
Knobs (feature-injection surface): --strategy, --remat, --microbatches,
--opt-state {float32,q8}.
"""

import argparse
import dataclasses
import json
import sys
import time
import traceback
from pathlib import Path


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    strategy: str = "",
    remat: str = "dots",
    microbatches: int = 1,
    opt_state_dtype: str = "float32",
    global_batch: int = 0,
    moe_dispatch: str = "",
    verbose: bool = True,
):
    """Lower + compile one cell; returns a JSON-able record."""
    import jax

    from repro import configs
    from repro.configs import shapes as SH
    from repro.core import roofline
    from repro.distributed import hlo
    from repro.distributed import sharding as S
    from repro.distributed import steps as ST
    from repro.hardware import MULTI_POD, SINGLE_POD
    from repro.launch.mesh import make_production_mesh
    from repro.train.optimizer import OptConfig

    import dataclasses as _dc

    cfg = configs.get_config(arch)
    if moe_dispatch and cfg.moe:
        cfg = _dc.replace(cfg, moe=_dc.replace(cfg.moe, dispatch=moe_dispatch))
    shape = SH.SHAPES[shape_name]
    if global_batch:
        shape = _dc.replace(shape, global_batch=global_batch)
    if not SH.applicable(cfg, shape):
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": "full-attention arch; long_500k inapplicable (DESIGN.md)"}
    system = MULTI_POD if multi_pod else SINGLE_POD
    strategy_name = strategy or S.default_strategy(cfg, shape.kind)
    strat = S.STRATEGIES[strategy_name]
    mesh = make_production_mesh(multi_pod=multi_pod)

    t0 = time.time()
    kw = {}
    if shape.kind == SH.TRAIN:
        kw = {
            "opt_cfg": OptConfig(state_dtype=opt_state_dtype),
            "remat": remat,
            "microbatches": microbatches,
        }
    elif shape.kind == SH.PREFILL:
        kw = {"remat": remat}
    bundle = ST.build_step(cfg, shape, mesh, strat, **kw)
    with mesh:
        lowered = bundle.lower()
        compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis()
    text = compiled.as_text()
    cost = hlo.analyze(text, n_devices=system.n_chips)

    def _tree_bytes(tree):
        import numpy as np
        return float(sum(
            np.prod(l.shape) * l.dtype.itemsize for l in jax.tree.leaves(tree)
        ))

    # The CPU backend cannot alias donated buffers (alias_size==0 here); on
    # the TPU target the declared donations (params/opt-state/decode-state)
    # WOULD alias, so subtract them for the steady-state HBM estimate.
    donated = sum(
        _tree_bytes(bundle.abstract_args[i]) for i in bundle.donate_argnums
    ) / system.n_chips  # args are global; memory_analysis is per-device
    raw_required = float(
        mem.argument_size_in_bytes + mem.temp_size_in_bytes + mem.output_size_in_bytes
        - mem.alias_size_in_bytes
    )
    hbm_required = max(
        float(mem.argument_size_in_bytes + mem.temp_size_in_bytes),
        raw_required - donated,
    )
    # Decode/prefill state traffic for the memory-usefulness floor.
    state_bytes = 0.0
    if shape.kind == SH.DECODE:
        state_bytes = _tree_bytes(bundle.abstract_args[1])
    elif shape.kind == SH.PREFILL:
        from repro.models import transformer as TMod

        state_bytes = _tree_bytes(
            jax.eval_shape(lambda: TMod.init_decode_state(cfg, shape.global_batch, shape.seq_len))
        ) / 2.0  # written once, not re-read
    rl = roofline.compute(
        cfg=cfg,
        arch=arch,
        shape_name=shape_name,
        shape_kind=shape.kind,
        seq_len=shape.seq_len,
        global_batch=shape.global_batch,
        system=system,
        strategy=strategy_name,
        cost=cost,
        hbm_required=hbm_required,
        state_bytes=state_bytes,
    )
    record = {
        "arch": arch,
        "shape": shape_name,
        "system": system.name,
        "strategy": strategy_name,
        "status": "ok",
        "compile_s": t_compile,
        "knobs": {
            "remat": remat, "microbatches": microbatches,
            "opt_state_dtype": opt_state_dtype,
            "global_batch": shape.global_batch,
            "moe_dispatch": (cfg.moe.dispatch if cfg.moe else ""),
        },
        "memory_analysis": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "hbm_required": hbm_required,
        },
        "xla_cost_analysis": {
            k: float(v) for k, v in (ca or {}).items()
            if isinstance(v, (int, float)) and ("flops" in k or "bytes access" in k)
        },
        "roofline": rl.metrics(),
        "collectives": rl.collectives,
        "loops": cost.loops,
        "dominant": rl.dominant,
        "suggestion": rl.suggestion(),
    }
    if verbose:
        print(f"== {arch} × {shape_name} on {system.name} [{strategy_name}] ==")
        print(f"  compile: {t_compile:.1f}s   HLO instrs≈{len(text.splitlines())}")
        print(f"  memory_analysis: args={mem.argument_size_in_bytes/1e9:.3f}GB "
              f"temp={mem.temp_size_in_bytes/1e9:.3f}GB "
              f"out={mem.output_size_in_bytes/1e9:.3f}GB "
              f"-> {hbm_required/1e9:.3f}GB/device "
              f"({'FITS' if rl.fits else 'OVER'} {system.chip.hbm_bytes/1e9:.0f}GB HBM)")
        print(f"  cost_analysis(XLA, loop-unaware): {record['xla_cost_analysis']}")
        print(f"  loop-aware/device: flops={cost.flops:.3e} bytes={cost.bytes:.3e} "
              f"coll={cost.collective_bytes:.3e}")
        print(f"  terms: compute={rl.t_compute*1e3:.3f}ms memory={rl.t_memory*1e3:.3f}ms "
              f"collective={rl.t_collective*1e3:.3f}ms -> dominant={rl.dominant}")
        print(f"  MODEL_FLOPS={rl.model_flops:.3e} useful_ratio={rl.useful_ratio:.3f} "
              f"mem_useful={rl.memory_useful_ratio:.3f} mfu={rl.mfu:.3f} "
              f"roofline_fraction={rl.roofline_fraction:.3f}")
        print(f"  -> {rl.suggestion()}")
    return record


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true", help="every applicable cell")
    ap.add_argument("--strategy", default="")
    ap.add_argument("--remat", default="dots")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--opt-state", default="float32", choices=["float32", "q8"])
    ap.add_argument("--global-batch", type=int, default=0)
    ap.add_argument("--moe-dispatch", default="", choices=["", "row", "global"])
    ap.add_argument("--out", default="", help="directory for JSON records")
    args = ap.parse_args(argv)

    from repro import configs
    from repro.configs import shapes as SH

    cells = []
    if args.all:
        for a in configs.ARCH_IDS:
            cfg = configs.get_config(a)
            for s in SH.SHAPES.values():
                if SH.applicable(cfg, s):
                    cells.append((a, s.name))
    else:
        if not (args.arch and args.shape):
            ap.error("--arch and --shape required (or --all)")
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    outdir = Path(args.out) if args.out else None
    if outdir:
        outdir.mkdir(parents=True, exist_ok=True)
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            try:
                rec = run_cell(
                    arch, shape, multi_pod=mp, strategy=args.strategy,
                    remat=args.remat, microbatches=args.microbatches,
                    opt_state_dtype=args.opt_state,
                    global_batch=args.global_batch,
                    moe_dispatch=args.moe_dispatch,
                )
            except Exception as e:  # noqa: BLE001
                failures += 1
                rec = {"arch": arch, "shape": shape, "status": "error",
                       "multi_pod": mp, "error": f"{type(e).__name__}: {e}",
                       "traceback": traceback.format_exc(limit=8)}
                print(f"!! {arch} × {shape} multi_pod={mp} FAILED: {e}",
                      file=sys.stderr)
            if outdir:
                tag = "2pod" if mp else "1pod"
                path = outdir / f"{arch}.{shape}.{tag}.json"
                path.write_text(json.dumps(rec, indent=2))
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
