"""What every plain float32 reference shares, and the comparison with it.

Each architecture's reference (``bench/arch/``) runs teacher-forced over a
whole sequence, one layer at a time, with every matrix product at full
float32 precision through ``mm``.  It imports nothing of the engine and
reads only the weights the benchmark drew.

``quant=True`` is the precision control: every operand of every matrix
product is rounded to float8 e4m3 with a per-tensor scale (per row for
activations) before the product.  It stands for the step down from the
configuration's bfloat16 that a later change could be tempted to take.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BUCKET = 512  # sequences are padded to a multiple of this (few compiles)
OUT_BLOCK = 512  # output positions compared per request (longest output)
Q_BLOCK = 512  # query rows per attention block (bounds the score matrix)
E4M3_MAX = 240.0  # largest finite e4m3 value with IEEE exponent rules


def fp8(x: jax.Array, axis=None) -> jax.Array:
    """Round ``x`` to float8 e4m3 (4 exponent, 3 mantissa bits) with a
    scale that maps its largest magnitude (over ``axis``) to the format's
    largest finite value.  ``reduce_precision`` rounds for certain: a
    cast to a narrow type and back may be folded away by the compiler."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / E4M3_MAX
    q = jax.lax.reduce_precision(x / s, exponent_bits=4, mantissa_bits=3)
    return q * s


def mm(spec: str, a: jax.Array, b: jax.Array, quant: bool,
       a_rows: bool = True) -> jax.Array:
    """einsum at full f32 precision; under ``quant`` both operands are
    first rounded through fp8 (``a`` per row when ``a_rows``)."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if quant:
        a = fp8(a, -1 if a_rows else None)
        b = fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def rms_norm(x, scale, eps):
    """RMSNorm of the last axis, in float32."""
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)


def rope(x, pos, theta):
    """Rotary embedding of (T, heads, head_dim) ``x`` at positions ``pos``
    (halves rotated, as the Llama block does)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@jax.jit
def _stats(ref, picks, other):
    """Per position: the reference's best logit, its logit of ``picks``,
    the largest |ref - other| and the largest |ref|."""
    picked = jnp.take_along_axis(ref, picks[:, None], axis=1)[:, 0]
    return (ref.max(axis=-1), picked, jnp.max(jnp.abs(ref - other), -1),
            jnp.max(jnp.abs(ref), -1))


def compare(ref: jax.Array, picks: np.ndarray, other, n: int) -> Dict:
    """Over the first ``n`` positions: how far below the reference's best
    logit each pick's lies (``gap``), and the largest |logit difference|
    to ``other`` as a share of the largest |reference logit| (``dev``)."""
    p = np.zeros((OUT_BLOCK,), np.int32)
    p[:len(picks)] = picks
    best, picked, diff, scale = (np.asarray(v)[:n] for v in _stats(
        ref, jnp.asarray(p), jnp.asarray(other, jnp.float32)))
    return {"gap": float(np.max(best - picked)),
            "dev": float(np.max(diff / scale))}
