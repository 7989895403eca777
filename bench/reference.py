"""Plain float32 reference of the served decoder models.

A Llama-style block as the Granite papers describe it (arXiv:2405.04324
for the dense code models, the Granite 3.0 report for the MoE ones):

    x = embed[tokens] * embedding_multiplier
    per layer:
        h = rmsnorm(x) ; q, k, v = h Wq, h Wk, h Wv ; rotary on q, k
        a = softmax(q k^T * attention_multiplier, causal) v   (grouped KV)
        x = x + residual_multiplier * a Wo
        h = rmsnorm(x)
        x = x + residual_multiplier * ffn(h)
          dense: (silu(h Wg) * h Wu) Wd
          MoE:   sum over the top-k experts of the renormalized softmax
                 router weight times that expert's SwiGLU
    logits = rmsnorm(x) E^T / logits_scaling

It runs teacher-forced over a whole sequence, one layer at a time, with
every matrix product at full float32 precision.  It imports nothing of the
engine and reads only the weights the benchmark drew.

``quant=True`` is the precision control: every operand of every matrix
product is rounded to float8 e4m3 with a per-tensor scale (per row for
activations) before the product.  It stands for the step down from the
configuration's bfloat16 that a later change could be tempted to take.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
BUCKET = 512  # sequences are padded to a multiple of this (few compiles)
OUT_BLOCK = 512  # output positions compared per request (longest output)
Q_BLOCK = 512  # query rows per attention block (bounds the score matrix)
E4M3_MAX = 240.0  # largest finite e4m3 value with IEEE exponent rules


@dataclass(frozen=True)
class Arch:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    rope_theta: float
    eps: float
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float

    @classmethod
    def from_model(cls, m: Dict) -> "Arch":
        heads = m["num_attention_heads"]
        hd = m.get("head_dim") or m["hidden_size"] // heads
        return cls(d=m["hidden_size"], layers=m["num_hidden_layers"],
                   heads=heads, kv_heads=m["num_key_value_heads"],
                   head_dim=hd, experts=m.get("num_local_experts", 0),
                   top_k=m.get("num_experts_per_tok", 0),
                   rope_theta=float(m["rope_theta"]),
                   eps=float(m["rms_norm_eps"]),
                   embedding_multiplier=float(
                       m.get("embedding_multiplier", 1.0)),
                   attention_multiplier=float(
                       m.get("attention_multiplier", hd ** -0.5)),
                   residual_multiplier=float(
                       m.get("residual_multiplier", 1.0)),
                   logits_scaling=float(m.get("logits_scaling", 1.0)))


def _fp8(x: jax.Array, axis=None) -> jax.Array:
    """Round ``x`` to float8 e4m3 (4 exponent, 3 mantissa bits) with a
    scale that maps its largest magnitude (over ``axis``) to the format's
    largest finite value.  ``reduce_precision`` rounds for certain: a
    cast to a narrow type and back may be folded away by the compiler."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / E4M3_MAX
    q = jax.lax.reduce_precision(x / s, exponent_bits=4, mantissa_bits=3)
    return q * s


def _mm(spec: str, a: jax.Array, b: jax.Array, quant: bool,
        a_rows: bool = True) -> jax.Array:
    """einsum at full f32 precision; under ``quant`` both operands are
    first rounded through fp8 (``a`` per row when ``a_rows``)."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if quant:
        a = _fp8(a, -1 if a_rows else None)
        b = _fp8(b)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, scale, eps):
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("a", "quant"))
def _layer(x, stacked, i, *, a: Arch, quant: bool):
    """One layer over the whole (padded) sequence ``x`` (T, d) f32."""
    w = {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
         for k, v in stacked.items()}
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _rms(x, w["ln1"], a.eps)
    q = _rope(_mm("td,dhk->thk", h, w["wq"], quant), pos, a.rope_theta)
    k = _rope(_mm("td,dhk->thk", h, w["wk"], quant), pos, a.rope_theta)
    v = _mm("td,dhk->thk", h, w["wv"], quant)
    G = a.heads // a.kv_heads
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)

    def block(qb_start):
        qb = jax.lax.dynamic_slice_in_dim(q, qb_start, Q_BLOCK, 0)
        s = _mm("qhk,thk->hqt", qb, k, quant, a_rows=False)
        s = s * a.attention_multiplier
        rows = qb_start + jnp.arange(Q_BLOCK)
        s = jnp.where(rows[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return _mm("hqt,thk->qhk", p, v, quant, a_rows=False)

    o = jax.lax.map(block, jnp.arange(0, T, Q_BLOCK))
    o = o.reshape(T, a.heads, a.head_dim)
    x = x + a.residual_multiplier * _mm("thk,hkd->td", o.reshape(
        T, a.heads, a.head_dim), w["wo"], quant, a_rows=False)
    h = _rms(x, w["ln2"], a.eps)
    if a.experts:
        probs = jax.nn.softmax(_mm("td,de->te", h, w["router"], quant), -1)
        top, idx = jax.lax.top_k(probs, a.top_k)
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        gates = jnp.zeros_like(probs).at[
            jnp.arange(T)[:, None], idx].set(top)
        g = _mm("td,edf->tef", h, w["wg"], quant)
        u = _mm("td,edf->tef", h, w["wu"], quant)
        y = _mm("tef,efd->ted", jax.nn.silu(g) * u, w["wd"], quant,
                a_rows=False)
        f = jnp.einsum("te,ted->td", gates, y, precision=HI)
    else:
        g = _mm("td,df->tf", h, w["wg"], quant)
        u = _mm("td,df->tf", h, w["wu"], quant)
        f = _mm("tf,fd->td", jax.nn.silu(g) * u, w["wd"], quant)
    return x + a.residual_multiplier * f


@partial(jax.jit, static_argnames=("a", "quant"))
def _rows(x, start, ln_f, embed, *, a: Arch, quant: bool):
    """Logits (f32) at the ``OUT_BLOCK`` positions from ``start``."""
    xs = jax.lax.dynamic_slice_in_dim(x, start, OUT_BLOCK, 0)
    h = _rms(xs, ln_f, a.eps)
    return _mm("td,vd->tv", h, embed, quant) / a.logits_scaling


@jax.jit
def _stats(ref, picks, other):
    """Per position: the reference's best logit, its logit of ``picks``,
    the largest |ref - other| and the largest |ref|."""
    picked = jnp.take_along_axis(ref, picks[:, None], axis=1)[:, 0]
    return (ref.max(axis=-1), picked, jnp.max(jnp.abs(ref - other), -1),
            jnp.max(jnp.abs(ref), -1))


def forward(a: Arch, w: Dict, tokens: List[int], first: int,
            quant: bool = False) -> jax.Array:
    """Final hidden states of a teacher-forced pass over ``tokens``,
    padded to a multiple of ``BUCKET`` that holds ``OUT_BLOCK`` positions
    from ``first``."""
    n = max(len(tokens), first + OUT_BLOCK)
    ids = np.zeros((-(-n // BUCKET) * BUCKET,), np.int32)
    ids[:len(tokens)] = tokens
    x = w["embed"][jnp.asarray(ids)].astype(jnp.float32)
    x = x * a.embedding_multiplier
    for i in range(a.layers):
        x = _layer(x, w["layers"], i, a=a, quant=quant)
    return x


def logits(a: Arch, w: Dict, x: jax.Array, first: int,
           quant: bool = False) -> jax.Array:
    """(OUT_BLOCK, vocab) logits at positions ``first ..`` of ``x``."""
    return _rows(x, first, w["ln_f"], w["embed"], a=a, quant=quant)


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
