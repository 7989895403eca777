"""What every architecture's seeded weights share.

The benchmark makes the weights itself, on the device in one program from
the seed (each ``bench/arch/`` module's ``draw``), so that the reference
it checks the engine against takes nothing the engine made.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

INIT_STD = 0.02  # every matrix ~ N(0, 0.02^2); norm scales are 1


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from any whole-number seed: ``SeedSequence``
    mixes all its bits, where ``PRNGKey`` would keep only the low 32."""
    state = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jnp.asarray(state, jnp.uint32)
