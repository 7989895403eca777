"""Pallas TPU kernel: flash-style attention with FlexAttention semantics.

The paper pairs the paged allocator with PyTorch FlexAttention: a JIT-fused
kernel whose sparsity/masking comes from user hooks.  This is the TPU
equivalent: a tiled online-softmax attention kernel whose

  * *block sparsity* comes from a precompiled ``BlockMask``
    (``kv_indices`` is a scalar-prefetch operand — the same indirection
    trick as the paged decode kernel: the grid only visits live KV tiles);
  * *element masking* comes from a traced ``mask_mod`` evaluated on tile
    index iotas — skipped entirely on tiles flagged ``is_full``;
  * *score shaping* comes from a traced ``score_mod`` (softcap, ALiBi, ...).

Grid: (B, H, num_q_blocks, max_kv_blocks) — kv innermost; accumulators in
VMEM scratch. GQA is handled by the k/v index_map (h → h // group).

Block shapes: q/o (1,1,q_blk,D), k/v (1,1,kv_blk,D) — q_blk=kv_blk=128 by
default so the (128,128)·(128,D) tile products run on full MXU tiles.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import flex
from repro.kernels import resolve_interpret

NEG_INF = -1e30


class _AuxView:
    """A scalar-prefetch aux operand as the mask/score mods see it.

    A lookup whose indices are all scalars (``lens[b]``, ``slopes[h]``)
    reads one SMEM scalar: Mosaic cannot load a whole scalar-prefetch
    array into vector registers.  A lookup with vector indices
    (``docs[b, q]``) loads the array first; Mosaic has no vector gather
    from SMEM, so such mods run only under the interpreter.
    """

    def __init__(self, ref):
        self.ref = ref

    def __getitem__(self, idx):
        idx = idx if isinstance(idx, tuple) else (idx,)
        if all(jnp.ndim(i) == 0 for i in idx):
            return self.ref[idx]
        return self.ref[...][idx]


def _flex_kernel(
    # scalar prefetch: block mask + aux tensors (FlexAttention "bias" trick)
    kv_num_blocks_ref,  # (nq,)
    kv_indices_ref,  # (nq, max_kv)
    is_full_ref,  # (nq, max_kv) int32
    *refs,  # *aux_refs (n_mask_aux + n_score_aux), q, k, v, o, m, l, acc
    scale: float,
    mask_fn,
    score_fn,
    n_mask_aux: int,
    n_score_aux: int,
    q_blk: int,
    kv_blk: int,
    q_len: int,
    kv_len: int,
):
    aux_refs = tuple(_AuxView(r) for r in refs[: n_mask_aux + n_score_aux])
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs[
        n_mask_aux + n_score_aux:]
    mask_aux = aux_refs[:n_mask_aux]
    score_aux = aux_refs[n_mask_aux:]

    def mask_mod(b, h, q, k):
        return mask_fn(b, h, q, k, *mask_aux)

    score_mod = None
    if score_fn is not None:
        def score_mod(s, b, h, q, k):
            return score_fn(s, b, h, q, k, *score_aux)

    b = pl.program_id(0)
    h = pl.program_id(1)
    qb = pl.program_id(2)
    j = pl.program_id(3)
    n_j = pl.num_programs(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if kv_indices_ref.ndim == 3:  # batched block mask
        kb = kv_indices_ref[b, qb, j]
        live = j < kv_num_blocks_ref[b, qb]
        full = is_full_ref[b, qb, j] > 0
    else:
        kb = kv_indices_ref[qb, j]
        live = j < kv_num_blocks_ref[qb]
        full = is_full_ref[qb, j] > 0

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (q_blk, D)
        k = k_ref[0, 0].astype(jnp.float32)  # (kv_blk, D)
        v = v_ref[0, 0].astype(jnp.float32)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)

        qi = qb * q_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 0)
        ki = kb * kv_blk + jax.lax.broadcasted_iota(jnp.int32, (q_blk, kv_blk), 1)
        if score_mod is not None:
            s = score_mod(s, b, h, qi, ki)
        # a boolean `where` does not legalize in Mosaic; `|` does
        mask = full | mask_mod(b, h, qi, ki)
        mask &= (qi < q_len) & (ki < kv_len)  # block-padding validity
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.where(mask, jnp.exp(s - m_new), 0.0)

        l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_j - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flex_attention_kernel(
    q: jax.Array,  # (B, H, Q, D)
    k: jax.Array,  # (B, Hkv, K, D)
    v: jax.Array,
    block_mask: flex.BlockMask,
    *,
    scale: float,
    mask_mod=flex.causal_mask,
    score_mod=None,
    q_len: int = 0,  # true (pre-padding) lengths; 0 = no padding
    kv_len: int = 0,
    interpret: Optional[bool] = None,
) -> jax.Array:
    B, H, Q, D = q.shape
    Hkv, K = k.shape[1], k.shape[2]
    q_len = q_len or Q
    kv_len = kv_len or K
    G = H // Hkv
    q_blk, kv_blk = block_mask.q_block, block_mask.kv_block
    assert Q % q_blk == 0 and K % kv_blk == 0, "wrapper must pad to blocks"
    nq = Q // q_blk
    max_kv = block_mask.kv_indices.shape[1]

    # unpack aux tensors out of AuxMod wrappers (→ scalar-prefetch operands)
    if isinstance(mask_mod, flex.AuxMod):
        mask_fn, mask_aux = mask_mod.fn, mask_mod.aux
    else:
        mask_fn, mask_aux = (lambda b, h, q, k: mask_mod(b, h, q, k)), ()
    if score_mod is None:
        score_fn, score_aux = None, ()
    elif isinstance(score_mod, flex.AuxMod):
        score_fn, score_aux = score_mod.fn, score_mod.aux
    else:
        score_fn, score_aux = (
            lambda s, b, h, q, k: score_mod(s, b, h, q, k)), ()
    n_aux = len(mask_aux) + len(score_aux)
    n_prefetch = 3 + n_aux

    def q_map(b, h, qb, j, *pref):
        return (b, h, qb, 0)

    def kv_map(b, h, qb, j, nb, idx, *pref):
        if idx.ndim == 3:
            return (b, h // G, idx[b, qb, j], 0)
        return (b, h // G, idx[qb, j], 0)

    kernel = functools.partial(
        _flex_kernel, scale=scale, mask_fn=mask_fn, score_fn=score_fn,
        n_mask_aux=len(mask_aux), n_score_aux=len(score_aux),
        q_blk=q_blk, kv_blk=kv_blk, q_len=q_len, kv_len=kv_len)

    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=n_prefetch,
            grid=(B, H, nq, max_kv),
            in_specs=[
                pl.BlockSpec((1, 1, q_blk, D), q_map),
                pl.BlockSpec((1, 1, kv_blk, D), kv_map),
                pl.BlockSpec((1, 1, kv_blk, D), kv_map),
            ],
            out_specs=pl.BlockSpec((1, 1, q_blk, D), q_map),
            scratch_shapes=[
                pltpu.VMEM((q_blk, 1), jnp.float32),
                pltpu.VMEM((q_blk, 1), jnp.float32),
                pltpu.VMEM((q_blk, D), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, Q, D), q.dtype),
        interpret=resolve_interpret(interpret),
    )(block_mask.kv_num_blocks, block_mask.kv_indices,
      block_mask.is_full.astype(jnp.int32), *mask_aux, *score_aux, q, k, v)
