"""Seeded weights, drawn by the benchmark on the device in one program.

The benchmark makes the weights itself, so that the reference it checks
the engine against takes nothing the engine made.  This module is the one
place that knows the engine's parameter layout (``to_engine``); the
reference reads the same arrays layer by layer through ``layer``.
"""

from __future__ import annotations

from functools import partial
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from flops import Shape

INIT_STD = 0.02  # every matrix ~ N(0, 0.02^2); norm scales are 1


def seed_key(seed: int) -> jax.Array:
    """A raw threefry key from any whole-number seed: ``SeedSequence``
    mixes all its bits, where ``PRNGKey`` would keep only the low 32."""
    state = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jnp.asarray(state, jnp.uint32)


def layer_shapes(s: Shape) -> Dict[str, tuple]:
    """Shapes of one layer's leaves, by the reference's names."""
    d, H, K, D = s.d, s.heads, s.kv_heads, s.head_dim
    out = {"ln1": (d,), "wq": (d, H, D), "wk": (d, K, D), "wv": (d, K, D),
           "wo": (H, D, d), "ln2": (d,)}
    if s.experts:
        E = s.experts
        out.update(router=(d, E), wg=(E, d, s.ff), wu=(E, d, s.ff),
                   wd=(E, s.ff, d))
    else:
        out.update(wg=(d, s.ff), wu=(d, s.ff), wd=(s.ff, d))
    return out


@partial(jax.jit, static_argnums=(0, 2))
def _draw(s: Shape, key: jax.Array, dtype) -> Dict:
    shapes = layer_shapes(s)
    keys = jax.random.split(key, len(shapes) + 1)
    stacked = {}
    for k, (name, shape) in zip(keys[1:], sorted(shapes.items())):
        full = (s.layers,) + shape
        if name.startswith("ln"):
            stacked[name] = jnp.ones(full, dtype)
        else:
            stacked[name] = (jax.random.normal(k, full, jnp.float32)
                             * INIT_STD).astype(dtype)
    embed = (jax.random.normal(keys[0], (s.vocab, s.d), jnp.float32)
             * INIT_STD).astype(dtype)
    return {"embed": embed, "ln_f": jnp.ones((s.d,), dtype),
            "layers": stacked}


def draw(s: Shape, seed: int, dtype=jnp.bfloat16) -> Dict:
    """All weights of the model, stacked by layer, drawn from ``seed``."""
    return _draw(s, seed_key(seed), jnp.dtype(dtype))


def layer(w: Dict, i: int) -> Dict:
    """Layer ``i``'s leaves by the reference's names (one copy of them)."""
    return {k: v[i] for k, v in w["layers"].items()}


def to_engine(w: Dict, s: Shape) -> Dict:
    """The engine's parameter tree over the same arrays (no copy): one
    scanned group of ``s.layers`` attention layers, tied embeddings."""
    L = w["layers"]
    group = {"ln1": {"scale": L["ln1"]}, "ln2": {"scale": L["ln2"]},
             "attn": {k: L[k] for k in ("wq", "wk", "wv", "wo")}}
    ffn = {k: L[k] for k in ("wg", "wu", "wd")}
    if s.experts:
        group["moe"] = dict(ffn, router=L["router"])
    else:
        group["mlp"] = ffn
    return {"embed": {"tok": w["embed"]}, "ln_f": {"scale": w["ln_f"]},
            "groups": {"0A": group}, "rem": {}}
