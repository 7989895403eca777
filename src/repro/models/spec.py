"""Parameter-spec system.

Model builders describe parameters as a pytree of ``ParamSpec`` leaves
(shape + logical sharding axes + init).  From one spec tree we derive:
  * initialised parameters        (``materialize``)
  * the logical-axes tree         (``axes_tree``)    → NamedShardings
  * ShapeDtypeStructs for dry-run (``abstract``)     → .lower() without RAM
keeping init, sharding, and dry-run shapes impossible to de-synchronise.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class ParamSpec(NamedTuple):
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"  # normal | zeros | ones | small_normal
    scale: float = 0.02


def _is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _init_leaf(spec: ParamSpec, key: jax.Array, dtype):
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "a_log":  # RG-LRU Λ init: a ∈ [0.9, 0.999]
        u = jax.random.uniform(key, spec.shape, jnp.float32, 0.9, 0.999)
        return jnp.log(u / (1 - u)).astype(dtype)
    scale = spec.scale
    if spec.init == "small_normal":
        scale = spec.scale / np.sqrt(max(spec.shape[-1], 1))
    return (jax.random.normal(key, spec.shape, jnp.float32) * scale
            ).astype(dtype)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _init_leaves(specs: Tuple[ParamSpec, ...], rng: jax.Array, dtype):
    # one program for the whole tree: each f32 draw fuses into its cast,
    # so only the ``dtype`` leaves are ever resident (a (layers, d_model,
    # d_ff) stack drawn eagerly would hold two f32 copies of itself), and
    # a model compiles one program instead of one per leaf shape
    keys = jax.random.split(rng, len(specs))
    return [_init_leaf(s, keys[i], dtype) for i, s in enumerate(specs)]


def materialize(spec_tree, rng: jax.Array, dtype=jnp.float32):
    leaves, treedef = jax.tree_util.tree_flatten(spec_tree, is_leaf=_is_spec)
    return jax.tree_util.tree_unflatten(
        treedef, _init_leaves(tuple(leaves), rng, jnp.dtype(dtype)))


def axes_tree(spec_tree):
    return jax.tree_util.tree_map(lambda s: tuple(s.axes), spec_tree,
                                  is_leaf=_is_spec)


def abstract(spec_tree, dtype=jnp.float32):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype), spec_tree,
        is_leaf=_is_spec)


def stack_specs(spec_tree, n: int, axis_name: Optional[str] = None):
    """Prepend a stacking (layer) dimension to every spec in the tree."""
    return jax.tree_util.tree_map(
        lambda s: ParamSpec((n,) + tuple(s.shape), (axis_name,) + tuple(s.axes),
                            s.init, s.scale),
        spec_tree, is_leaf=_is_spec)
