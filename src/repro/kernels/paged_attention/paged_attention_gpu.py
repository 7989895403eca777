"""Pallas GPU kernel: fused paged decode attention (Triton lowering).

GPU counterpart of the TPU decode kernel (`paged_attention.py`) — the
paper's actual deployment target: a FlexAttention-style fused kernel that
*gathers scattered KV data* inside the attention loop (§III-B).  Where the
TPU lowering must route the page→HBM translation through BlockSpec
``index_map``s so Mosaic's DMA pipeline streams pages into VMEM, the
Triton lowering gathers *inside* the kernel: the block table is a plain
device array and each KV block's pages are fetched with dynamically
indexed ``tl.load``s (Pallas ref indexing by a traced page id), exactly
how GPU PagedAttention kernels address non-contiguous physical blocks.

Design (mirrors the TPU kernel's v2 contract)
=============================================

Grid layout
-----------
::

    grid = (batch, kv_heads, num_splits)

One CUDA block per (b, h, s) slot.  There is no grid axis for KV blocks:
each slot walks its ``blocks_per_split`` KV blocks with an in-kernel
``fori_loop``, gathering ``pages_per_block`` scattered pages per step via
block-table indexed loads and folding them into an online softmax held in
registers.  All three grid axes are embarrassingly parallel — the GPU
analogue of the TPU kernel's megacore ``dimension_semantics``: different
splits of the *same* sequence land on different SMs, which is the whole
point of flash-decoding split-K for batch=1 long-context decode.

Partition & partial contract
----------------------------
`decode_partition` is shared with the TPU kernel, so both backends put
bit-identical page ranges in each split, and every ``(b, h, s)`` slot
emits the same un-normalised ``(m, l, acc)`` partial that
`ref.paged_attention_partials_ref` specifies.  The split-K merge is the
*same* `combine_partials` the TPU pipeline uses — jnp epilogue or the
fused Pallas combine kernel — completely unchanged, which is what lets
`tests/test_combine_conformance.py` gate both backends with one oracle.

Dead entries / ragged lengths
-----------------------------
Table ranks are pre-clamped on the host (`_blocked_tables`, shared): a
dead slot re-reads an already-live page, so gathers never touch pages
past ``lens[b]`` and no load needs a mask.  On the dense path the
``fori_loop`` trip count is clamped to the split's *live* block count —
wholly-dead padding blocks are never gathered or scored (the GPU
analogue of the TPU kernel's ``pl.when`` + elided DMAs) and a fully-empty
split does zero trips, emitting the ``(NEG_INF, 0, 0)`` init partial
that drops out of the combine exactly.  Per-token liveness masks a
partially-live block's scores to ``NEG_INF`` — all identical in effect
to the TPU kernel.

Matmul shapes
-------------
``tl.dot`` needs M ≥ 16 but GQA groups are small (G ∈ 1..8), so scores
and the p·V contraction use a broadcast multiply-reduce when G < 16 (the
same trick as jax's GPU decode-attention kernel) and a real MMA otherwise.

Validation
----------
Off-GPU the kernel runs through the Pallas interpreter (CPU CI exercises
the full ppb × splits × variant conformance sweep); on a real GPU it
compiles through ``plgpu.CompilerParams``.  Real-GPU
``interpret=False`` validation is an open ROADMAP item, mirroring the
TPU-hardware one.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import triton as plgpu

from repro.kernels import resolve_interpret
from repro.kernels.paged_attention.paged_attention import (
    NEG_INF, _blocked_tables, _prefill_q_blocks, combine_partials,
    combine_prefill_partials, decode_partition)

# Triton launch shape: warps per CTA / software pipeline depth for the
# gather+dot loop.  Modest defaults — one (G, ppb·P) tile per CTA is a
# small working set; deeper pipelining mostly hides the scattered loads.
_NUM_WARPS = 4
_NUM_STAGES = 2


def _dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """(M, K) @ (K, N) in f32.  tl.dot requires M ≥ 16; GQA decode has
    M = G ∈ 1..8, so small M uses a broadcast multiply-reduce (VPU-ish)
    instead of an MMA — identical math, no Triton shape constraint."""
    if a.shape[0] < 16:
        return jnp.sum(a[:, :, None] * b[None, :, :], axis=1)
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _decode_kernel_gpu(
    tables_ref,  # (B, n_blocks, ppb) int32 — rank-clamped table slice
    lens_ref,  # (B,) int32
    q_ref,  # (1, 1, G, D) block for this (b, h)
    k_ref,  # (num_pages, n_kv, P, D) — whole pool, gathered in-kernel
    v_ref,
    m_out,  # (1, 1, 1, G, 1)
    l_out,  # (1, 1, 1, G, 1)
    acc_out,  # (1, 1, 1, G, D)
    *,
    pages_per_block: int,
    blocks_per_split: int,
    scale: float,
    window: int,
    softcap: float,
    kv_scale: float,
):
    ppb = pages_per_block
    b = pl.program_id(0)
    h = pl.program_id(1)
    s = pl.program_id(2)
    page_size = k_ref.shape[2]
    G, D = q_ref.shape[2], q_ref.shape[3]

    q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, D)
    L = lens_ref[b]
    slot = jax.lax.broadcasted_iota(jnp.int32, (page_size,), 0)
    if window > 0:
        ring = -(-window // page_size) + 1
        cur_page = jnp.maximum(L - 1, 0) // page_size
        # bounded ring: any slot may be live — walk the whole split
        n_trips = blocks_per_split
    else:
        # dead-block skip (the GPU analogue of the TPU kernel's pl.when +
        # DMA elision): only the blocks covering ceil(L / page_size) live
        # pages are walked; a split wholly past the live range does zero
        # trips and emits the init (NEG_INF, 0, 0) partial.
        n_live_blocks = ((L + page_size - 1) // page_size + ppb - 1) // ppb
        n_trips = jnp.clip(n_live_blocks - s * blocks_per_split, 0,
                           blocks_per_split)

    def body(blk, carry):
        m_prev, l_prev, acc_prev = carry  # (G, 1), (G, 1), (G, D)
        block_rank = s * blocks_per_split + blk
        first_page = block_rank * ppb
        ks, vs, lives = [], [], []
        for j in range(ppb):
            pg = first_page + j
            if window > 0:
                # ring slot → logical position (see ref.ring_slot_positions)
                lpage = cur_page - ((cur_page - pg) % ring)
                pos = lpage * page_size + slot
                pos = jnp.where(pos >= L, pos - ring * page_size, pos)
                lives.append((pos >= 0) & (pos < L) & (pos >= L - window)
                             & (pg < ring))
            else:
                pos = pg * page_size + slot
                lives.append(pos < L)
            # the paged gather: one dynamically indexed load per scattered
            # page — the table entry computes the tl.load base pointer
            page = tables_ref[b, block_rank, j]
            ks.append(k_ref[page, h])  # (P, D)
            vs.append(v_ref[page, h])
        live = jnp.concatenate(lives)  # (ppb·P,)
        k = jnp.concatenate(ks, axis=0).astype(jnp.float32)
        v = jnp.concatenate(vs, axis=0).astype(jnp.float32)
        if kv_scale > 0:  # int8 pages: dequantize the gathered tile
            k = k * kv_scale
            v = v * kv_scale

        s_ = _dot(q, k.T)  # (G, ppb·P)
        if softcap > 0:
            s_ = softcap * jnp.tanh(s_ / softcap)
        s_ = jnp.where(live[None, :], s_, NEG_INF)

        m_cur = jnp.max(s_, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.where(live[None, :], jnp.exp(s_ - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(pexp, axis=1, keepdims=True)
        acc_new = acc_prev * alpha + _dot(pexp, v)
        return m_new, l_new, acc_new

    init = (jnp.full((G, 1), NEG_INF, jnp.float32),
            jnp.zeros((G, 1), jnp.float32),
            jnp.zeros((G, D), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, n_trips, body, init)
    m_out[0, 0, 0] = m
    l_out[0, 0, 0] = l
    acc_out[0, 0, 0] = acc


def paged_attention_partials_gpu(
    q: jax.Array,  # (B, n_kv, G, D)
    k_pages: jax.Array,  # (num_pages, n_kv, P, D)
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, max_pages)
    lens: jax.Array,  # (B,)
    *,
    scale: float,
    window: int = 0,
    softcap: float = 0.0,
    interpret: Optional[bool] = None,
    kv_scale: float = 0.0,
    pages_per_block: int = 1,
    num_splits: int = 1,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Split-K partials, same contract as the TPU kernel's
    `paged_attention_partials`: ((B,n_kv,S,G) m, (B,n_kv,S,G) l,
    (B,n_kv,S,G,D) acc) — f32."""
    B, n_kv, G, D = q.shape
    num_pages, _, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]

    ppb, _, S, bps = decode_partition(max_pages, pages_per_block, num_splits)
    padded_pages = S * bps * ppb

    tables3d = _blocked_tables(
        block_tables, lens, num_pages=num_pages, page_size=page_size,
        window=window, padded_pages=padded_pages, pages_per_block=ppb)

    kernel = functools.partial(
        _decode_kernel_gpu, pages_per_block=ppb, blocks_per_split=bps,
        scale=scale, window=window, softcap=softcap, kv_scale=kv_scale)

    whole = lambda arr: pl.BlockSpec(arr.shape,
                                     lambda b, h, s: (0,) * arr.ndim)
    part_map = lambda b, h, s: (b, h, s, 0, 0)
    m, l, acc = pl.pallas_call(
        kernel,
        grid=(B, n_kv, S),
        in_specs=[
            whole(tables3d),
            whole(lens),
            pl.BlockSpec((1, 1, G, D), lambda b, h, s: (b, h, 0, 0)),
            whole(k_pages),  # pools stay in GMEM; gathered per table entry
            whole(v_pages),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, G, 1), part_map),
            pl.BlockSpec((1, 1, 1, G, 1), part_map),
            pl.BlockSpec((1, 1, 1, G, D), part_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, n_kv, S, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, S, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, S, G, D), jnp.float32),
        ],
        compiler_params=plgpu.CompilerParams(
            num_warps=_NUM_WARPS, num_stages=_NUM_STAGES),
        interpret=resolve_interpret(interpret, backend="gpu"),
    )(tables3d, lens.astype(jnp.int32), q, k_pages, v_pages)
    return m[..., 0], l[..., 0], acc


def _prefill_kernel_gpu(
    tables_ref,  # (B, n_blocks, ppb) int32 — rank-clamped table slice
    lens_ref,  # (B,) int32 — kv_lens (cached tokens incl. the chunk)
    qstart_ref,  # (B,) int32 — absolute position of chunk token 0
    q_ref,  # (1, 1, 1, R, D) block for this (b, h, nq)
    k_ref,  # (num_pages, n_kv, P, D) — whole pool, gathered in-kernel
    v_ref,
    m_out,  # (1, 1, 1, 1, R, 1)
    l_out,
    acc_out,  # (1, 1, 1, 1, R, D)
    *,
    pages_per_block: int,
    blocks_per_split: int,
    q_block: int,
    group: int,
    scale: float,
    softcap: float,
    kv_scale: float,
):
    """Chunked-prefill GPU body: one CTA per (b, h, nq, s) slot, in-kernel
    ``fori_loop`` over the split's KV blocks with block-table gathers —
    the decode kernel's structure with a ``q_block·G``-row score tile and
    a causal trip-count clamp (blocks wholly past the Q-block's last
    query are never gathered)."""
    ppb = pages_per_block
    b = pl.program_id(0)
    h = pl.program_id(1)
    nq = pl.program_id(2)
    s = pl.program_id(3)
    page_size = k_ref.shape[2]
    R, D = q_ref.shape[3], q_ref.shape[4]

    q = q_ref[0, 0, 0].astype(jnp.float32) * scale  # (R, D)
    L = lens_ref[b]
    q0 = qstart_ref[b]
    slot = jax.lax.broadcasted_iota(jnp.int32, (page_size,), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (R,), 0)
    qpos = q0 + nq * q_block + row // group  # (R,)
    qpos_max = q0 + nq * q_block + q_block - 1
    # live + causal block bound: only blocks covering tokens < min(L,
    # qpos_max+1) contribute — the rest do zero trips (init partial).
    kv_hi = jnp.minimum(L, qpos_max + 1)
    n_live_blocks = ((kv_hi + page_size - 1) // page_size + ppb - 1) // ppb
    n_trips = jnp.clip(n_live_blocks - s * blocks_per_split, 0,
                       blocks_per_split)

    def body(blk, carry):
        m_prev, l_prev, acc_prev = carry  # (R, 1), (R, 1), (R, D)
        block_rank = s * blocks_per_split + blk
        first_page = block_rank * ppb
        ks, vs, poss = [], [], []
        for j in range(ppb):
            pg = first_page + j
            poss.append(pg * page_size + slot)
            page = tables_ref[b, block_rank, j]
            ks.append(k_ref[page, h])  # (P, D)
            vs.append(v_ref[page, h])
        kvpos = jnp.concatenate(poss)  # (ppb·P,)
        k = jnp.concatenate(ks, axis=0).astype(jnp.float32)
        v = jnp.concatenate(vs, axis=0).astype(jnp.float32)
        if kv_scale > 0:
            k = k * kv_scale
            v = v * kv_scale

        s_ = _dot(q, k.T)  # (R, ppb·P)
        if softcap > 0:
            s_ = softcap * jnp.tanh(s_ / softcap)
        live = (kvpos < L)[None, :] & (kvpos[None, :] <= qpos[:, None])
        s_ = jnp.where(live, s_, NEG_INF)

        m_cur = jnp.max(s_, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.where(live, jnp.exp(s_ - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(pexp, axis=1, keepdims=True)
        acc_new = acc_prev * alpha + _dot(pexp, v)
        return m_new, l_new, acc_new

    init = (jnp.full((R, 1), NEG_INF, jnp.float32),
            jnp.zeros((R, 1), jnp.float32),
            jnp.zeros((R, D), jnp.float32))
    m, l, acc = jax.lax.fori_loop(0, n_trips, body, init)
    m_out[0, 0, 0, 0] = m
    l_out[0, 0, 0, 0] = l
    acc_out[0, 0, 0, 0] = acc


def paged_prefill_partials_gpu(
    q: jax.Array,  # (B, C, n_heads, D)
    k_pages: jax.Array,  # (num_pages, n_kv, P, D)
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, max_pages)
    kv_lens: jax.Array,  # (B,)
    q_start: jax.Array,  # (B,)
    *,
    scale: float,
    softcap: float = 0.0,
    interpret: Optional[bool] = None,
    kv_scale: float = 0.0,
    pages_per_block: int = 1,
    num_splits: int = 1,
    q_block: int = 1,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked-prefill split-K partials (Triton lowering) — identical
    contract to the TPU `paged_prefill_partials`; gated by the same
    `ref.paged_prefill_ref` oracle."""
    B, C, n_heads, D = q.shape
    num_pages, n_kv, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    G = n_heads // n_kv

    ppb, _, S, bps = decode_partition(max_pages, pages_per_block, num_splits)
    padded_pages = S * bps * ppb
    qb5, NQ = _prefill_q_blocks(q, n_kv, q_block)
    R = q_block * G

    tables3d = _blocked_tables(
        block_tables, kv_lens, num_pages=num_pages, page_size=page_size,
        window=0, padded_pages=padded_pages, pages_per_block=ppb)

    kernel = functools.partial(
        _prefill_kernel_gpu, pages_per_block=ppb, blocks_per_split=bps,
        q_block=q_block, group=G, scale=scale, softcap=softcap,
        kv_scale=kv_scale)

    whole = lambda arr: pl.BlockSpec(arr.shape,
                                     lambda b, h, nq, s: (0,) * arr.ndim)
    part_map = lambda b, h, nq, s: (b, h, nq, s, 0, 0)
    m, l, acc = pl.pallas_call(
        kernel,
        grid=(B, n_kv, NQ, S),
        in_specs=[
            whole(tables3d),
            whole(kv_lens),
            whole(q_start),
            pl.BlockSpec((1, 1, 1, R, D), lambda b, h, nq, s: (b, h, nq, 0, 0)),
            whole(k_pages),
            whole(v_pages),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, 1, R, 1), part_map),
            pl.BlockSpec((1, 1, 1, 1, R, 1), part_map),
            pl.BlockSpec((1, 1, 1, 1, R, D), part_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, n_kv, NQ, S, R, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, NQ, S, R, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, NQ, S, R, D), jnp.float32),
        ],
        compiler_params=plgpu.CompilerParams(
            num_warps=_NUM_WARPS, num_stages=_NUM_STAGES),
        interpret=resolve_interpret(interpret, backend="gpu"),
    )(tables3d, kv_lens.astype(jnp.int32), q_start.astype(jnp.int32), qb5,
      k_pages, v_pages)
    return m[..., 0], l[..., 0], acc


def paged_prefill_kernel_gpu(
    q: jax.Array,  # (B, C, n_heads, D)
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,
    kv_lens: jax.Array,
    q_start: jax.Array,
    *,
    scale: float,
    softcap: float = 0.0,
    interpret: Optional[bool] = None,
    kv_scale: float = 0.0,
    pages_per_block: int = 1,
    num_splits: int = 1,
    q_block: int = 1,
    combine_mode: Optional[str] = None,
) -> jax.Array:
    """Full chunked-prefill attention (GPU): Triton partials + the shared
    split-K combine (backend-independent, same oracle)."""
    m, l, acc = paged_prefill_partials_gpu(
        q, k_pages, v_pages, block_tables, kv_lens, q_start, scale=scale,
        softcap=softcap, interpret=interpret, kv_scale=kv_scale,
        pages_per_block=pages_per_block, num_splits=num_splits,
        q_block=q_block)
    return combine_prefill_partials(m, l, acc, q.shape[1], q_block,
                                    dtype=q.dtype, mode=combine_mode,
                                    interpret=interpret)


def paged_attention_kernel_gpu(
    q: jax.Array,  # (B, n_kv, G, D) — q heads grouped by kv head
    k_pages: jax.Array,  # (num_pages, n_kv, P, D)
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, max_pages) int32 (may contain -1)
    lens: jax.Array,  # (B,)
    *,
    scale: float,
    window: int = 0,
    softcap: float = 0.0,
    interpret: Optional[bool] = None,
    kv_scale: float = 0.0,
    pages_per_block: int = 1,
    num_splits: int = 1,
    combine_mode: Optional[str] = None,
) -> jax.Array:
    """Full GPU decode: Triton partials + the shared split-K combine."""
    m, l, acc = paged_attention_partials_gpu(
        q, k_pages, v_pages, block_tables, lens, scale=scale, window=window,
        softcap=softcap, interpret=interpret, kv_scale=kv_scale,
        pages_per_block=pages_per_block, num_splits=num_splits)
    # the combine contract is backend-independent — same kernel/epilogue,
    # same oracle (`ref.combine_partials_ref`), zero GPU-specific code
    return combine_partials(m, l, acc, dtype=q.dtype, mode=combine_mode,
                            interpret=interpret)
