"""Attention dispatch — the paper's "drop-in deployability" surface (§I-B).

One entry point per phase; a config flag (`paged_attention`) switches between
the paged implementation and the contiguous baseline, exactly like the
paper's FMS integration ("via configuration flags, requiring no model
re-training or architecture edits").

  * ``prefill_attention`` — full-sequence causal/windowed attention
    (flex kernel or jnp fallback) used by training and prompt prefill;
  * ``decode_attention``  — one token against the paged KV pools
    (Pallas kernel / oracle), optionally distributed with a
    flash-decoding-style online-softmax combine across mesh axes
    (the `kvp` scheme — our beyond-paper extension);
  * ``decode_attention_contiguous`` — the paper's baseline: a max-length
    pre-allocated cache.

All functions are GQA-aware and sharding-agnostic (they may run inside
`shard_map`; `kv_psum_axes` enables the cross-shard combine).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import flex
from repro.kernels.flex_attention.ops import flex_attention
from repro.kernels.paged_attention.ops import paged_attention, paged_prefill
from repro.kernels.paged_attention.ref import gather_pages, ring_slot_positions

# re-export: serving/bench code sizes decode grids through this module
from repro.kernels.paged_attention.ops import choose_decode_params  # noqa: F401
from repro.kernels.paged_attention.ops import choose_prefill_params  # noqa: F401


def prefill_attention(
    q: jax.Array,  # (B, S, H, D)
    k: jax.Array,  # (B, S, Hkv, D)
    v: jax.Array,
    *,
    window: int = 0,
    softcap: float = 0.0,
    lens: Optional[jax.Array] = None,
    causal: bool = True,
    impl: str = "jnp",
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Full-sequence attention for training / prefill.  Returns (B, S, H, D)."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    if impl == "ring":
        # context parallelism: sequence-sharded online-softmax attention
        # with K/V rotating over the "model" axis (DESIGN.md / §Perf H2)
        from repro.distributed.ring import ring_attention, ring_available
        if ring_available(S):
            return ring_attention(q, k, v, lens=lens, causal=causal,
                                  window=window, softcap=softcap)
        impl = "chunked"  # no mesh / indivisible seq: local fallback
    if impl == "pallas":
        return _flex_prefill(q, k, v, lens, window=window, softcap=softcap,
                             causal=causal, interpret=interpret)
    mask_mod, score_mod = _prefill_mods(lens, window, softcap, causal)
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    if impl == "chunked":
        # flash-style two-level chunking: O(q_chunk·kv_chunk) live scores.
        # This is the path the multi-pod dry-run lowers for long sequences
        # (the dense path would claim O(S²) temp bytes at 32k).
        out = _chunked_attention(qt, kt, vt, mask_mod, score_mod)
    else:
        # jnp path: identical math, O(S²) scores — fine for smoke tests
        out = _dense_attention(qt, kt, vt, mask_mod, score_mod)
    return out.transpose(0, 2, 1, 3)


def _prefill_mods(lens, window: int, softcap: float, causal: bool):
    """(mask_mod, score_mod) of a prefill: causal or windowed, padded."""
    mods = []
    if causal:
        mods.append(flex.sliding_window_mask(window) if window > 0
                    else flex.causal_mask)
    elif window > 0:
        mods.append(flex.sliding_window_mask(window))
    if lens is not None:
        mods.append(flex.padding_mask(lens))
    mask_mod = flex.and_masks(*mods) if mods else flex.full_mask
    score_mod = flex.softcap_score(softcap) if softcap > 0 else None
    return mask_mod, score_mod


@functools.partial(jax.jit, static_argnames=("window", "softcap", "causal",
                                             "interpret"))
def _flex_prefill(q, k, v, lens, *, window: int, softcap: float,
                  causal: bool, interpret: Optional[bool]):
    """The flex kernel under one jit: the mods are closures made per
    call, so an eager kernel launch would trace and compile anew for
    every layer of every prefill; here it compiles once per shape."""
    mask_mod, score_mod = _prefill_mods(lens, window, softcap, causal)
    out = flex_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                         v.transpose(0, 2, 1, 3), mask_mod=mask_mod,
                         score_mod=score_mod, window=window,
                         interpret=interpret)
    return out.transpose(0, 2, 1, 3)


def _dense_attention(q, k, v, mask_mod, score_mod):
    """(B,H,Q,D)x(B,Hkv,K,D) dense masked attention in f32 accumulation."""
    B, H, Q, D = q.shape
    Hkv, K = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = 1.0 / np.sqrt(D)
    qg = (q * scale).reshape(B, Hkv, G, Q, D)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                   preferred_element_type=jnp.float32)
    bi = jnp.arange(B)[:, None, None, None, None]
    hi = jnp.arange(H).reshape(Hkv, G)[None, :, :, None, None]
    qi = jnp.arange(Q)[None, None, None, :, None]
    ki = jnp.arange(K)[None, None, None, None, :]
    if score_mod is not None:
        s = score_mod(s, bi, hi, qi, ki)
    m = mask_mod(bi, hi, qi, ki)
    s = jnp.where(m, s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", w.astype(v.dtype), v)
    return out.reshape(B, H, Q, D)


def _chunked_attention(q, k, v, mask_mod, score_mod,
                       q_chunk: int = 512, kv_chunk: int = 1024):
    """(B,H,Q,D)x(B,Hkv,K,D) online-softmax attention in (qc × kc) tiles.

    Pure-JAX flash: an outer ``lax.map`` over q-chunks and an inner
    ``lax.scan`` over kv-chunks keep live score buffers at
    (B,Hkv,G,qc,kc) regardless of sequence length.  Mask/score mods are
    evaluated per tile on index arrays (the FlexAttention contract), so any
    composed mod works unchanged.  Rectangular iteration (no tile skipping)
    — the Pallas kernel does the skipping on real hardware; here the HLO
    FLOPs over-count causal attention by ≤2×, which the roofline notes.
    """
    B, H, Q, D = q.shape
    Hkv, K = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = 1.0 / np.sqrt(D)
    qc = min(q_chunk, Q)
    kc = min(kv_chunk, K)
    nq = -(-Q // qc)
    nk = -(-K // kc)
    Qp, Kp = nq * qc, nk * kc
    qpad = jnp.pad(q, ((0, 0), (0, 0), (0, Qp - Q), (0, 0)))
    kpad = jnp.pad(k, ((0, 0), (0, 0), (0, Kp - K), (0, 0)))
    vpad = jnp.pad(v, ((0, 0), (0, 0), (0, Kp - K), (0, 0)))
    # (nq, B, Hkv, G, qc, D) / (nk, B, Hkv, kc, D)
    qt = (qpad.reshape(B, Hkv, G, nq, qc, D).transpose(3, 0, 1, 2, 4, 5)
          * scale).astype(q.dtype)
    kt = kpad.reshape(B, Hkv, nk, kc, D).transpose(2, 0, 1, 3, 4)
    vt = vpad.reshape(B, Hkv, nk, kc, D).transpose(2, 0, 1, 3, 4)

    bi = jnp.arange(B)[:, None, None, None, None]
    hi = jnp.arange(H).reshape(Hkv, G)[None, :, :, None, None]

    def q_block(args):
        qi, qb = args  # qb: (B, Hkv, G, qc, D)
        q_idx = (qi * qc + jnp.arange(qc))[None, None, None, :, None]

        def kv_body(carry, kv):
            m, l, acc = carry
            kj, kb, vb = kv
            k_idx = (kj * kc + jnp.arange(kc))[None, None, None, None, :]
            s = jnp.einsum("bhgqd,bhkd->bhgqk", qb, kb,
                           preferred_element_type=jnp.float32)
            if score_mod is not None:
                s = score_mod(s, bi, hi, q_idx, k_idx)
            live = mask_mod(bi, hi, q_idx, k_idx) & (k_idx < K)
            s = jnp.where(live, s, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.where(live, jnp.exp(s - m_safe[..., None]), 0.0)
            alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l = l * alpha + jnp.sum(p, axis=-1)
            acc = acc * alpha[..., None] + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l, acc), None

        init = (jnp.full((B, Hkv, G, qc), -jnp.inf),
                jnp.zeros((B, Hkv, G, qc)),
                jnp.zeros((B, Hkv, G, qc, D)))
        (m, l, acc), _ = jax.lax.scan(
            kv_body, init, (jnp.arange(nk), kt, vt))
        return acc / jnp.maximum(l, 1e-30)[..., None]

    out = jax.lax.map(q_block, (jnp.arange(nq), qt))  # (nq,B,Hkv,G,qc,D)
    out = out.transpose(1, 2, 3, 0, 4, 5).reshape(B, H, Qp, D)
    return out[:, :, :Q].astype(q.dtype)


def prefill_attention_paged(
    q: jax.Array,  # (B, C, H, D) — one prompt *chunk* per sequence
    k_pages: jax.Array,  # (num_pages, Hkv, P, D)
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, max_pages)
    kv_lens: jax.Array,  # (B,) cached tokens incl. the chunk
    q_start: jax.Array,  # (B,) absolute position of chunk token 0
    *,
    softcap: float = 0.0,
    impl: str = "ref",
    interpret: Optional[bool] = None,
    kv_scale: float = 0.0,
    pages_per_block: Optional[int] = None,
    num_splits: Optional[int] = None,
    combine_mode: Optional[str] = None,
    backend: Optional[str] = None,
    q_block: Optional[int] = None,
) -> jax.Array:
    """Chunked paged prefill attention — the prompt-phase counterpart of
    `decode_attention`.  The chunk's K/V must already sit in the pages
    (write-then-attend, like the decode path): queries attend causally
    over the cached prefix pages *and* the chunk's own causal part, all
    read through the block table.  ``impl="pallas"`` runs the prefix-aware
    Q-block × KV-block kernel (TPU or GPU lowering per ``backend``);
    anything else runs the jnp oracle.  Returns (B, C, H, D)."""
    kernel_impl = "pallas" if impl == "pallas" else "ref"
    return paged_prefill(
        q, k_pages, v_pages, block_tables, kv_lens, q_start,
        softcap=softcap, impl=kernel_impl, interpret=interpret,
        kv_scale=kv_scale, pages_per_block=pages_per_block,
        num_splits=num_splits, combine_mode=combine_mode, backend=backend,
        q_block=q_block)


def prefill_attention_windowed_chunk(
    q: jax.Array,  # (B, C, H, D)
    k_new: jax.Array,  # (B, C, Hkv, D) — the chunk's fresh K/V
    v_new: jax.Array,
    k_pages: jax.Array,  # (num_pages, Hkv, P, D) — ring pools, pre-write
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, ring)
    q_start: jax.Array,  # (B,) cached prefix length (chunk NOT yet written)
    q_lens: jax.Array,  # (B,) live chunk tokens
    *,
    window: int,
    softcap: float = 0.0,
    kv_scale: float = 0.0,
) -> jax.Array:
    """Sliding-window chunked prefill (attend-then-write fallback).

    Ring-paged 'W' layers cannot use the write-then-attend kernel: a long
    chunk's writes wrap the ring and overwrite prefix slots earlier
    queries still need.  Instead the chunk attends over the *intact* ring
    prefix (gathered, the slots hold exactly the last ``ring·P ≥ window``
    prefix positions) plus its own fresh K/V, and the caller scatters the
    chunk into the ring afterwards.  Bounded working set — the ring is
    small by construction, so a jnp path suffices."""
    B, C, H, D = q.shape
    num_pages, Hkv, P, _ = k_pages.shape
    G = H // Hkv
    scale = 1.0 / np.sqrt(D)

    ring = -(-window // P) + 1
    # mixed dense/windowed models share one table sized for the dense
    # layers — only the first `ring` columns are ring slots here
    block_tables = block_tables[:, :ring]
    S = block_tables.shape[1] * P

    safe = jnp.clip(block_tables, 0, num_pages - 1)
    kpre = jax.lax.optimization_barrier(gather_pages(k_pages, safe))
    vpre = jax.lax.optimization_barrier(gather_pages(v_pages, safe))
    if kv_scale > 0:
        kpre = (kpre.astype(jnp.float32) * kv_scale).astype(q.dtype)
        vpre = (vpre.astype(jnp.float32) * kv_scale).astype(q.dtype)

    # positions the ring slots hold w.r.t. the *prefix* (length q_start)
    pre_pos = ring_slot_positions(q_start, P, ring, S)  # (B, S)
    qpos = q_start[:, None] + jnp.arange(C)[None, :]  # (B, C)
    live_pre = ((pre_pos >= 0) & (pre_pos < q_start[:, None])
                & (block_tables >= 0)[:, :, None].repeat(P, 2).reshape(B, S))
    # sliding window: k ≤ q and q − k < window (flex.sliding_window_mask)
    mask_pre = (live_pre[:, None, :]
                & (qpos[:, :, None] - pre_pos[:, None, :] < window))
    ci = jnp.arange(C)
    mask_new = ((ci[None, :] <= ci[:, None])
                & (ci[:, None] - ci[None, :] < window))[None]  # (1, C, C)
    mask_new = mask_new & (ci[None, None, :] < q_lens[:, None, None])
    mask = jnp.concatenate(
        [mask_pre, jnp.broadcast_to(mask_new, (B, C, C))], axis=2)

    k_all = jnp.concatenate([kpre, k_new.astype(kpre.dtype)], axis=1)
    v_all = jnp.concatenate([vpre, v_new.astype(vpre.dtype)], axis=1)
    qg = (q * scale).reshape(B, C, Hkv, G, D)
    s = jnp.einsum("bckgd,bskd->bkgcs", qg, k_all.astype(q.dtype)
                   ).astype(jnp.float32)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(mask[:, None, None, :, :], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(jnp.isnan(w), 0.0, w)
    out = jnp.einsum("bkgcs,bskd->bckgd", w, v_all.astype(jnp.float32))
    return out.reshape(B, C, H, D).astype(q.dtype)


def decode_attention(
    q: jax.Array,  # (B, H, D) — one token per sequence
    k_pages: jax.Array,  # (num_pages, Hkv, P, D)
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, max_pages)
    lens: jax.Array,  # (B,)
    *,
    window: int = 0,
    softcap: float = 0.0,
    impl: str = "ref",
    kv_psum_axes: Tuple[str, ...] = (),
    page_stride: int = 1,
    page_offset=0,
    interpret: Optional[bool] = None,
    kv_scale: float = 0.0,
    pages_per_block: Optional[int] = None,
    num_splits: Optional[int] = None,
    combine_mode: Optional[str] = None,
    backend: Optional[str] = None,
) -> jax.Array:
    """Paged decode attention; distributed combine over ``kv_psum_axes``.

    When ``kv_psum_axes`` is non-empty this runs *inside* `shard_map` with
    the page dim sharded across those axes: each shard computes a partial
    online-softmax (m, l, o) over its local pages and the partials merge
    with the numerically-stable two-pass combine (flash-decoding on a mesh,
    `collectives.merge_flash_partials` — the same reduction implementation
    the single-device split-K kernel uses).
    ``page_stride``/``page_offset`` describe round-robin page striping:
    local table slot j holds *logical* page j·stride + offset.

    ``pages_per_block`` / ``num_splits`` are the single-device Pallas
    kernel's KV-block width and split-K factor (``None`` → auto-tuned,
    see `choose_decode_params`); the kvp path's split-K happens across the
    mesh instead, so they only apply to the local kernel.  ``combine_mode``
    selects the split-K merge implementation on *both* paths ("pallas" =
    fused combine kernel, "jnp" = epilogue; None → auto).  ``backend``
    picks the local kernel's lowering ("tpu" scalar-prefetch pipeline or
    "gpu" Triton in-kernel gather; None → auto from the running platform).
    """
    if not kv_psum_axes:
        return paged_attention(q, k_pages, v_pages, block_tables, lens,
                               window=window, softcap=softcap, impl=impl,
                               interpret=interpret, kv_scale=kv_scale,
                               pages_per_block=pages_per_block,
                               num_splits=num_splits,
                               combine_mode=combine_mode, backend=backend)

    # --- local partials ---------------------------------------------------
    m_l, l_l, o_l = _partial_decode(q, k_pages, v_pages, block_tables, lens,
                                    window=window, softcap=softcap,
                                    page_stride=page_stride,
                                    page_offset=page_offset,
                                    kv_scale=kv_scale)
    # --- cross-shard combine (shared with the split-K kernel) --------------
    from repro.distributed.collectives import merge_flash_partials
    return merge_flash_partials(m_l, l_l, o_l, kv_psum_axes,
                                combine_mode=combine_mode,
                                out_dtype=q.dtype, interpret=interpret)


def _partial_decode(q, k_pages, v_pages, block_tables, lens, *, window=0,
                    softcap=0.0, page_stride=1, page_offset=0,
                    kv_scale=0.0):
    """Un-normalised decode attention over the local page shard.

    Returns (m, l, o·l) with shapes ((B,H), (B,H), (B,H,D)) — f32.
    block_tables here maps to *local* physical pages; dead entries are -1.
    lens is the per-sequence *global* length; with page striping, local
    table slot j covers logical page j·page_stride + page_offset.
    """
    B, H, D = q.shape
    num_pages, Hkv, P, _ = k_pages.shape
    max_pages = block_tables.shape[1]
    S = max_pages * P
    scale = 1.0 / np.sqrt(D)

    safe = jnp.clip(block_tables, 0, num_pages - 1)
    # optimization_barrier: keeps any downstream dtype convert pinned to the
    # gathered page-working-set instead of being hoisted onto the whole pool
    # (the CPU backend's float-normalization pass would otherwise shadow the
    # full pool in f32 — pool-sized dead memory; harmless no-op on TPU).
    k = jax.lax.optimization_barrier(gather_pages(k_pages, safe))
    v = jax.lax.optimization_barrier(gather_pages(v_pages, safe))
    if kv_scale > 0:  # int8 pools: dequantize the gathered working set
        k = (k.astype(jnp.float32) * kv_scale).astype(q.dtype)
        v = (v.astype(jnp.float32) * kv_scale).astype(q.dtype)

    if window > 0:
        assert page_stride == 1, "windowed caches are never page-striped"
        ring = -(-window // P) + 1
        pos = ring_slot_positions(lens, P, ring, S)
        live = (pos >= 0) & (pos < lens[:, None]) & (pos >= lens[:, None] - window)
        # table may be wider than the ring (mixed dense/windowed models);
        # slots past the ring never hold this layer's KV
        live &= (jnp.arange(S) // P < ring)[None, :]
    else:
        slot = jnp.arange(S)
        pos = (slot // P * page_stride + page_offset) * P + slot % P
        pos = jnp.broadcast_to(pos[None, :], (B, S))
        live = pos < lens[:, None]
    live &= (block_tables >= 0)[:, :, None].repeat(P, 2).reshape(B, S)

    G = H // Hkv
    # keep K/V in their storage dtype (bf16 on TPU — MXU inputs) and
    # accumulate in f32 via preferred_element_type: casting the pools
    # instead would let XLA hoist a full-pool f32 convert out of the layer
    # scan (2× pool bytes of dead memory).
    qg = (q.astype(jnp.float32) * scale).reshape(B, Hkv, G, D).astype(q.dtype)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k,
                   preferred_element_type=jnp.float32)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(live[:, None, None, :], s, -jnp.inf)
    m = jnp.max(s, axis=-1)  # (B, Hkv, G)
    m_safe = jnp.where(jnp.isfinite(m), m, -1e30)
    p = jnp.where(live[:, None, None, :], jnp.exp(s - m_safe[..., None]), 0.0)
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return (m_safe.reshape(B, H), l.reshape(B, H), o.reshape(B, H, D))


def decode_attention_contiguous(
    q: jax.Array,  # (B, H, D)
    k: jax.Array,  # (B, max_len, Hkv, D)
    v: jax.Array,
    lens: jax.Array,  # (B,)
    *,
    window: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    """The paper's baseline: decode against a max-length contiguous cache."""
    B, H, D = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    G = H // Hkv
    scale = 1.0 / np.sqrt(D)
    pos = jnp.arange(S)[None, :]
    live = pos < lens[:, None]
    if window > 0:
        live &= pos >= lens[:, None] - window
    qg = (q * scale).reshape(B, Hkv, G, D)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k.astype(q.dtype),
                   preferred_element_type=jnp.float32)
    if softcap > 0:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(live[:, None, None, :], s, -1e30)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w.astype(v.dtype), v)
    return out.reshape(B, H, D)
