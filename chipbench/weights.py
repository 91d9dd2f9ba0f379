"""Seeds for the architectures' weight makers (``chipbench/arch/``)."""
from __future__ import annotations

import jax
import numpy as np


def key_of(seed: int) -> jax.Array:
    """A JAX key from any non-negative seed, 32 bits or more."""
    word = int(np.random.default_rng([seed, 3]).integers(0, 2 ** 31 - 1))
    return jax.random.PRNGKey(word)
