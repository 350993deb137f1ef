"""How every family draws the weights it serves from ``--seed``.

Each layer's slice of each leaf is drawn from its own key: the seed's key
folded with a CRC of the leaf's path (``_key``) and then with the layer
index.  So the program's stacked tree comes from one jitted call, and the
reference can draw any single layer again without the rest.  Draws are
uniform with the fan-in standard deviation, in the dtype served
(``_uniform``).  The tree itself, its paths and shapes, is the family's
(``chipbench/families/``).
"""

from __future__ import annotations

import math
import zlib

from chipbench.traffic import rng_for


def weight_seed(seed: int) -> int:
    """A 31-bit key seed from any integer seed (a JAX key keeps 32 bits)."""
    return int(rng_for(seed, 3).integers(0, 2**31 - 1))


def base_key(seed: int):
    """The key all weights derive from.  Jitted draws take it as an
    argument, so one compiled program serves every seed."""
    import jax

    return jax.random.key(weight_seed(seed))


def _key(key, path: str):
    import jax

    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def _uniform(key, shape, std, dtype):
    import jax
    import jax.numpy as jnp

    a = std * math.sqrt(3.0)
    return jax.random.uniform(key, shape, jnp.float32, -a, a).astype(dtype)
