"""Pure-jnp oracle for the paged decode-attention kernel.

Implements Alg.1 GATHER + standard masked attention: materialise each
sequence's K/V from its pages, then softmax(q·Kᵀ)·V.  This is the
"numerical equivalence" baseline the paper validates against (§IV-B3).

Also provides the split-K oracle pair used to validate the flash-decoding
path of the blocked kernel: ``paged_attention_partials_ref`` computes the
per-partition un-normalised ``(m, l, acc)`` softmax partials over a
contiguous range of pages, and ``combine_partials_ref`` merges them with
the numerically-stable correction — the reference for the kernel-side
combine in ``paged_attention.combine_partials``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def ring_slot_positions(lens: jax.Array, page_size: int, ring: int,
                        n_slots: int) -> jax.Array:
    """Logical position held by each ring slot for a sliding-window cache.

    Slot s = (page j, offset o) holds the *latest* position p with
    (p // page_size) % ring == j and p % page_size == o and p < len.
    Returns (B, n_slots) positions (may exceed len-1 → dead, mask upstream).
    """
    s = jnp.arange(n_slots)
    j = s // page_size  # ring page index
    o = s % page_size
    L = lens[:, None]
    # latest page index l with l % ring == j and l*ps + o < L
    cur_page = jnp.maximum(L - 1, 0) // page_size
    # candidate page: largest l <= cur_page with l ≡ j (mod ring)
    l = cur_page - ((cur_page - j) % ring)
    pos = l * page_size + o
    # if that position is >= L, the slot's live token is one ring earlier
    pos = jnp.where(pos >= L, pos - ring * page_size, pos)
    return pos  # negative ⇒ slot never written


def gather_pages(pages: jax.Array, tables: jax.Array) -> jax.Array:
    """Alg.1 GATHER: (num_pages, n_kv, P, D) pool × (B, n) in-range page
    ids → contiguous (B, n·P, n_kv, D) K or V."""
    B, n = tables.shape
    _, n_kv, P, D = pages.shape
    g = pages[tables].transpose(0, 1, 3, 2, 4)  # (B, n, P, n_kv, D)
    return g.reshape(B, n * P, n_kv, D)


def paged_attention_ref(
    q: jax.Array,  # (B, n_heads, head_dim) — one query token per sequence
    k_pages: jax.Array,  # (num_pages, n_kv_heads, page_size, head_dim)
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, max_pages) int32, NULL = -1
    lens: jax.Array,  # (B,) int32 — cached tokens incl. the current one
    *,
    scale: Optional[float] = None,
    window: int = 0,  # >0 ⇒ sliding-window over a ring of pages
    softcap: float = 0.0,
    kv_scale: float = 0.0,  # >0: int8 pools, dequantize gathered slices
) -> jax.Array:
    B, n_heads, head_dim = q.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(head_dim)
    scores, live, v = _gathered_scores(
        q, k_pages, v_pages, block_tables, lens, scale=scale, window=window,
        softcap=softcap, kv_scale=kv_scale)
    scores = jnp.where(live[:, None, None, :], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)  # fully-masked rows
    out = jnp.einsum("bkgs,bskd->bkgd", w, v.astype(jnp.float32))
    return out.reshape(B, n_heads, head_dim).astype(q.dtype)


def _gathered_scores(q, k_pages, v_pages, block_tables, lens, *,
                     scale, window, softcap, kv_scale):
    """Shared prologue of the full oracle AND the split-K partials oracle
    (both must validate the same gather/mask/softcap semantics): gathered
    K/V, softcapped f32 scores, live mask.

    Returns (scores (B,Hkv,G,S) f32, live (B,S), v (B,S,Hkv,D)).
    """
    B, n_heads, head_dim = q.shape
    num_pages, n_kv, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    S = max_pages * page_size

    safe = jnp.clip(block_tables, 0, num_pages - 1)
    k = jax.lax.optimization_barrier(gather_pages(k_pages, safe))
    v = jax.lax.optimization_barrier(gather_pages(v_pages, safe))
    if kv_scale > 0:
        k = (k.astype(jnp.float32) * kv_scale).astype(q.dtype)
        v = (v.astype(jnp.float32) * kv_scale).astype(q.dtype)

    if window > 0:
        ring = -(-window // page_size) + 1
        pos = ring_slot_positions(lens, page_size, ring, S)
        live = (pos >= 0) & (pos < lens[:, None]) & (pos >= lens[:, None] - window)
        # mixed dense/windowed tables are wider than the ring — slots past
        # it belong to the dense layers' pages, never this layer's ring
        # (the Pallas kernels mask the same way: ``pg < ring``)
        live &= (jnp.arange(S) // page_size < ring)[None, :]
    else:
        pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
        live = pos < lens[:, None]
    live &= (block_tables >= 0)[:, :, None].repeat(page_size, 2).reshape(B, S)

    g = n_heads // n_kv
    qg = q.reshape(B, n_kv, g, head_dim) * scale
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(q.dtype)
                        ).astype(jnp.float32)
    if softcap > 0:
        scores = softcap * jnp.tanh(scores / softcap)
    return scores, live, v


def paged_prefill_ref(
    q: jax.Array,  # (B, C, n_heads, head_dim) — one prompt *chunk* per seq
    k_pages: jax.Array,  # (num_pages, n_kv, page_size, head_dim)
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, max_pages) int32, NULL = -1
    kv_lens: jax.Array,  # (B,) — cached tokens incl. the current chunk
    q_start: jax.Array,  # (B,) — absolute position of chunk token 0
    *,
    scale: Optional[float] = None,
    softcap: float = 0.0,
    kv_scale: float = 0.0,
) -> jax.Array:
    """Oracle for *chunked paged prefill*: a chunk of ``C`` query tokens
    attends causally over the sequence's paged KV cache.

    Contract (write-then-attend, mirroring the decode path): the chunk's
    K/V have already been scattered into the pages, so the cache holds
    ``kv_lens[b]`` tokens and query token ``i`` sits at absolute position
    ``q_start[b] + i``.  It attends over cached positions ``<= q_start+i``
    — the prefix written by earlier chunks *and* the causal part of its
    own chunk, all read back through the block table (Alg.1 GATHER).
    Rows past the live chunk (``q_start + i >= kv_lens``) are padding;
    their output is unspecified (finite, ignored by callers).

    ``q_start == 0`` and ``kv_lens == C`` is whole-prompt prefill;
    ``C == 1`` degenerates to `paged_attention_ref` at ``lens=kv_lens``.
    Sliding-window (ring-paged) layers are handled by the jnp fallback in
    `core.attention` — the ring overwrites make "read the chunk back from
    pages" ill-defined there.
    """
    B, C, n_heads, head_dim = q.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(head_dim)
    num_pages, n_kv, page_size, _ = k_pages.shape
    S = block_tables.shape[1] * page_size
    g = n_heads // n_kv

    safe = jnp.clip(block_tables, 0, num_pages - 1)
    k = jax.lax.optimization_barrier(gather_pages(k_pages, safe))
    v = jax.lax.optimization_barrier(gather_pages(v_pages, safe))
    if kv_scale > 0:
        k = (k.astype(jnp.float32) * kv_scale).astype(q.dtype)
        v = (v.astype(jnp.float32) * kv_scale).astype(q.dtype)

    pos = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    live_kv = pos < kv_lens[:, None]
    live_kv &= (block_tables >= 0)[:, :, None].repeat(page_size, 2).reshape(B, S)
    qpos = q_start[:, None] + jnp.arange(C)[None, :]  # (B, C)
    causal = pos[:, None, :] <= qpos[:, :, None]  # (B, C, S)
    live = live_kv[:, None, :] & causal

    qg = q.reshape(B, C, n_kv, g, head_dim) * scale
    scores = jnp.einsum("bckgd,bskd->bkgcs", qg, k.astype(q.dtype)
                        ).astype(jnp.float32)
    if softcap > 0:
        scores = softcap * jnp.tanh(scores / softcap)
    scores = jnp.where(live[:, None, None, :, :], scores, -jnp.inf)
    w = jax.nn.softmax(scores, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)  # fully-masked rows (padding)
    out = jnp.einsum("bkgcs,bskd->bckgd", w, v.astype(jnp.float32))
    return out.reshape(B, C, n_heads, head_dim).astype(q.dtype)


def paged_prefill_partials_ref(
    q: jax.Array,  # (B, C, n_heads, head_dim)
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, max_pages)
    kv_lens: jax.Array,  # (B,)
    q_start: jax.Array,  # (B,)
    *,
    scale: Optional[float] = None,
    softcap: float = 0.0,
    kv_scale: float = 0.0,
    num_splits: int = 1,
    pages_per_block: int = 1,
    q_block: int = 1,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Split-K oracle for the chunked-prefill kernels: per-(q-block, split)
    un-normalised ``(m, l, acc)`` partials over the same KV-block ranges
    `decode_partition` assigns — the identical partial contract the decode
    kernels emit, with the GQA row axis widened to ``q_block·G`` rows
    (row ``r`` = chunk token ``r // G``, head group ``r % G``).

    Returns (m, l, acc) shaped ((B,Hkv,NQ,S,R), (B,Hkv,NQ,S,R),
    (B,Hkv,NQ,S,R,D)) with ``NQ = ceil(C / q_block)``, ``R = q_block·G``
    — f32, directly mergeable by ``combine_partials`` over axis S after
    folding NQ into the batch axis.
    """
    NEG_INF = -1e30
    B, C, n_heads, head_dim = q.shape
    n_kv = k_pages.shape[1]
    page_size = k_pages.shape[2]
    max_pages = block_tables.shape[1]
    S_tok = max_pages * page_size
    scale = float(scale if scale is not None else 1.0 / np.sqrt(head_dim))
    g = n_heads // n_kv

    from repro.kernels.paged_attention.paged_attention import decode_partition
    ppb, _, ns, bps = decode_partition(max_pages, pages_per_block, num_splits)
    chunk = bps * ppb * page_size
    qb = max(1, min(int(q_block), C))
    nq = -(-C // qb)
    Cp = nq * qb

    qpad = jnp.pad(q, ((0, 0), (0, Cp - C), (0, 0), (0, 0)))
    qg = qpad.reshape(B, nq, qb, n_kv, g, head_dim) * scale
    safe = jnp.clip(block_tables, 0, k_pages.shape[0] - 1)
    k = gather_pages(k_pages, safe)
    v = gather_pages(v_pages, safe)
    if kv_scale > 0:
        k = (k.astype(jnp.float32) * kv_scale).astype(q.dtype)
        v = (v.astype(jnp.float32) * kv_scale).astype(q.dtype)
    pos = jnp.broadcast_to(jnp.arange(S_tok)[None, :], (B, S_tok))
    live_kv = pos < kv_lens[:, None]
    live_kv &= (block_tables >= 0)[:, :, None].repeat(page_size, 2
                                                      ).reshape(B, S_tok)
    qpos = q_start[:, None] + jnp.arange(Cp)[None, :]  # (B, Cp)

    # (B, n_kv, nq, qb, g, S) scores, rows r = t·G + g as the kernels emit
    scores = jnp.einsum("bntkgd,bskd->bkntgs", qg, k.astype(q.dtype)
                        ).astype(jnp.float32)
    if softcap > 0:
        scores = softcap * jnp.tanh(scores / softcap)
    live = (live_kv[:, None, :] & (pos[:, None, :] <= qpos[:, :, None])
            ).reshape(B, nq, qb, S_tok)  # (B, nq, qb, S)
    live = live[:, None, :, :, None, :]  # (B, 1, nq, qb, 1, S)

    ms, ls, accs = [], [], []
    for s in range(ns):
        lo, hi = s * chunk, min((s + 1) * chunk, S_tok)
        if lo >= hi:
            shape = (B, n_kv, nq, qb * g)
            ms.append(jnp.full(shape, NEG_INF, jnp.float32))
            ls.append(jnp.zeros(shape, jnp.float32))
            accs.append(jnp.zeros(shape + (head_dim,), jnp.float32))
            continue
        sl = jnp.where(live[..., lo:hi], scores[..., lo:hi], NEG_INF)
        m = jnp.max(sl, axis=-1)
        m = jnp.where(m > NEG_INF / 2, m, NEG_INF)
        p = jnp.where(live[..., lo:hi], jnp.exp(sl - m[..., None]), 0.0)
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bkntgs,bskd->bkntgd", p,
                         v[:, lo:hi].astype(jnp.float32))
        ms.append(m.reshape(B, n_kv, nq, qb * g))
        ls.append(l.reshape(B, n_kv, nq, qb * g))
        accs.append(acc.reshape(B, n_kv, nq, qb * g, head_dim))
    m = jnp.stack(ms, axis=3)  # (B, Hkv, NQ, S, R)
    l = jnp.stack(ls, axis=3)
    acc = jnp.stack(accs, axis=3)  # (B, Hkv, NQ, S, R, D)
    return m, l, acc


def paged_attention_partials_ref(
    q: jax.Array,  # (B, n_heads, head_dim)
    k_pages: jax.Array,  # (num_pages, n_kv, page_size, head_dim)
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, max_pages)
    lens: jax.Array,  # (B,)
    *,
    scale: Optional[float] = None,
    window: int = 0,
    softcap: float = 0.0,
    kv_scale: float = 0.0,
    num_splits: int = 1,
    pages_per_block: int = 1,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Split-K oracle: per-partition un-normalised softmax partials.

    The page list is cut at KV-*block* granularity into ``num_splits``
    contiguous ranges — the identical partitioning the kernel's split-K
    grid axis uses (blocks of ``pages_per_block`` pages, then
    ``ceil(n_blocks / num_splits)`` blocks per split), so per-split
    partials are directly comparable.  The last partition may be ragged
    and a wholly-dead partition yields (NEG_INF, 0, 0), which drops out
    of the combine exactly.

    Returns (m, l, acc) with GQA-grouped shapes
    ((B,Hkv,S,G), (B,Hkv,S,G), (B,Hkv,S,G,D)) — f32.
    """
    NEG_INF = -1e30
    B, n_heads, head_dim = q.shape
    n_kv = k_pages.shape[1]
    page_size = k_pages.shape[2]
    max_pages = block_tables.shape[1]
    S_tok = max_pages * page_size
    scale = float(scale if scale is not None else 1.0 / np.sqrt(head_dim))

    scores, live, v = _gathered_scores(
        q, k_pages, v_pages, block_tables, lens, scale=scale, window=window,
        softcap=softcap, kv_scale=kv_scale)

    from repro.kernels.paged_attention.paged_attention import decode_partition
    ppb, _, ns, bps = decode_partition(max_pages, pages_per_block, num_splits)
    chunk = bps * ppb * page_size

    g = n_heads // n_kv
    ms, ls, accs = [], [], []
    for s in range(ns):
        lo, hi = s * chunk, min((s + 1) * chunk, S_tok)
        if lo >= hi:  # split made of padding blocks only — dead partition
            ms.append(jnp.full((B, n_kv, g), NEG_INF, jnp.float32))
            ls.append(jnp.zeros((B, n_kv, g), jnp.float32))
            accs.append(jnp.zeros((B, n_kv, g, head_dim), jnp.float32))
            continue
        sl = scores[..., lo:hi]
        lv = live[:, None, None, lo:hi]
        sl = jnp.where(lv, sl, NEG_INF)
        m = jnp.max(sl, axis=-1)
        m = jnp.where(m > NEG_INF / 2, m, NEG_INF)  # wholly-dead partition
        p = jnp.where(lv, jnp.exp(sl - m[..., None]), 0.0)
        l = jnp.sum(p, axis=-1)
        acc = jnp.einsum("bkgs,bskd->bkgd", p,
                         v[:, lo:hi].astype(jnp.float32))
        ms.append(m)
        ls.append(l)
        accs.append(acc)
    m = jnp.stack(ms, axis=2)  # (B, Hkv, ns, G)
    l = jnp.stack(ls, axis=2)
    acc = jnp.stack(accs, axis=2)  # (B, Hkv, ns, G, D)
    return m, l, acc


def combine_partials_ref(m: jax.Array, l: jax.Array, acc: jax.Array
                         ) -> jax.Array:
    """Reference flash-decoding combine over the split axis (axis=2).

    m, l: (B, Hkv, S, G); acc: (B, Hkv, S, G, D).  Returns (B, H, D) f32.
    """
    m_g = jnp.max(m, axis=2, keepdims=True)
    corr = jnp.exp(m - m_g)
    l_g = jnp.sum(l * corr, axis=2)
    o = jnp.sum(acc * corr[..., None], axis=2)
    o = o / jnp.maximum(l_g, 1e-30)[..., None]
    B, n_kv, g, D = o.shape
    return o.reshape(B, n_kv * g, D)
