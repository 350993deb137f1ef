"""One rehearsal run of a cell with a fault planted in the program under
the timed path; ``test_chipbench_faults.py`` runs it in a process of its own.

    python fault_child.py <fault> <cell> <seed> [<rehearsal arrival rate>]
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def plant(fault: str) -> None:
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T

    if fault == "state_unchanged":        # the decode step returns its state unchanged
        decode = T.decode_step

        def step(params, cfg, state, batch, idx):
            return decode(params, cfg, state, batch, idx)[0], state
        T.decode_step = step
    elif fault == "half_batch":           # prefill computes half the batch, copied over the rest
        prefill = T.prefill

        def step(params, cfg, batch, **kw):
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            logits, state = prefill(params, cfg, half, **kw)
            twice = lambda a, axis: jnp.concatenate([a, a], axis=axis)
            return twice(logits, 0), jax.tree.map(lambda a: twice(a, 1), state)
        T.prefill = step
    elif fault == "token_altered":        # every decoded token becomes token 0
        decode = T.decode_step

        def step(params, cfg, state, batch, idx):
            logits, state = decode(params, cfg, state, batch, idx)
            return logits.at[..., 0].add(1e4), state
        T.decode_step = step
    else:
        raise ValueError(fault)


def main() -> int:
    fault, cell, seed = sys.argv[1], sys.argv[2], sys.argv[3]
    from chipbench import loader, run

    if len(sys.argv) > 4:
        load = loader.load_cell

        def load_cell(*a, **kw):
            c = load(*a, **kw)
            c.workload["rehearse"]["traffic"]["rate_rps"] = float(sys.argv[4])
            return c
        loader.load_cell = load_cell
    plant(fault)
    return run.main(["--workload", cell, "--seed", seed, "--seconds", "1",
                     "--trace", "0", "--rehearse"])


if __name__ == "__main__":
    sys.exit(main())
