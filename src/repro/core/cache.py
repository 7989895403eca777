"""Paged KV cache: page pools + block tables, shared across layers.

Pools are shaped (n_layers, num_pages, kv_heads, page_size, head_dim):
head-major within a page, so each (page, kv head) pair is one contiguous
(page_size, head_dim) tile — the block the Pallas kernels stream.
All sequences of a batch share one pool (the paper's *global KV cache*);
the same block table row addresses every layer's pool (standard paged-KV
layout — one indirection, L pools).

Three access paths:
  * ``write_prefill``  — scatter a whole prompt's K/V into its pages;
  * ``write_decode``   — scatter one new token per sequence (Alg.1 ASSIGN);
  * ``gather``         — materialise contiguous K/V (Alg.1 GATHER; the
    reference path — the Pallas kernel reads pages *in place* instead).

Sliding-window layers reuse pages as a ring: logical page index wraps modulo
the window's page count, so a 'W' layer's cache is bounded regardless of
sequence length (DESIGN.md §5 — RecurrentGemma local attention, and the
beyond-paper `swa` long-context variant for dense models).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import paging
from repro.core.paging import PageState
from repro.kernels.paged_attention.ref import gather_pages


class PagedKVCache(NamedTuple):
    k_pages: jax.Array  # (L, num_pages, kv_heads, page_size, head_dim)
    v_pages: jax.Array  # (L, num_pages, kv_heads, page_size, head_dim)
    state: PageState

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[3]

    @property
    def num_pages(self) -> int:
        return self.k_pages.shape[1]


def init_cache(n_layers: int, num_pages: int, page_size: int, kv_heads: int,
               head_dim: int, max_seqs: int, max_pages_per_seq: int,
               dtype=jnp.float32) -> PagedKVCache:
    shape = (n_layers, num_pages, kv_heads, page_size, head_dim)
    return PagedKVCache(
        k_pages=jnp.zeros(shape, dtype),
        v_pages=jnp.zeros(shape, dtype),
        state=paging.init_state(num_pages, max_seqs, max_pages_per_seq),
    )


def _scatter_tokens(pages: jax.Array, phys_pages: jax.Array, offsets: jax.Array,
                    vals: jax.Array) -> jax.Array:
    """pages: (num_pages, H, P, D); phys/offsets: (...,); vals: (..., H, D)."""
    flat_pages = phys_pages.reshape(-1)
    flat_off = offsets.reshape(-1)
    flat_vals = vals.reshape(-1, *vals.shape[-2:])
    # drop writes through NULL pages (unallocated → scheduler bug upstream)
    oob = jnp.where(flat_pages < 0, pages.shape[0], flat_pages)
    # the two index arrays straddle the head slice, so the updated
    # elements are laid out (N, H, D) — the shape of flat_vals
    return pages.at[oob, :, flat_off].set(flat_vals, mode="drop")


def write_decode(cache: PagedKVCache, layer: int, seq_ids: jax.Array,
                 positions: jax.Array, k_new: jax.Array, v_new: jax.Array,
                 window: int = 0) -> PagedKVCache:
    """Append one token per sequence at ``positions`` (Alg.1 ASSIGN).

    k_new/v_new: (B, kv_heads, head_dim).  ``window>0`` wraps the logical
    page index (ring of pages) for bounded sliding-window layers.
    """
    ps = cache.page_size
    logical = positions // ps
    if window > 0:
        ring = -(-window // ps) + 1
        logical = logical % ring
    phys = cache.state.block_tables[seq_ids, logical]
    off = positions % ps
    return cache._replace(
        k_pages=cache.k_pages.at[layer].set(
            _scatter_tokens(cache.k_pages[layer], phys, off, k_new)),
        v_pages=cache.v_pages.at[layer].set(
            _scatter_tokens(cache.v_pages[layer], phys, off, v_new)),
    )


def write_layer_decode(k_pages_l: jax.Array, v_pages_l: jax.Array,
                       state: PageState, seq_ids: jax.Array,
                       positions: jax.Array, k_new: jax.Array,
                       v_new: jax.Array, window: int = 0
                       ) -> Tuple[jax.Array, jax.Array]:
    """Per-layer variant for use inside the layer scan (pools as scan xs)."""
    ps = k_pages_l.shape[2]
    logical = positions // ps
    if window > 0:
        ring = -(-window // ps) + 1
        logical = logical % ring
    phys = state.block_tables[seq_ids, logical]
    off = positions % ps
    return (_scatter_tokens(k_pages_l, phys, off, k_new),
            _scatter_tokens(v_pages_l, phys, off, v_new))


def write_layer_prefill(k_pages_l: jax.Array, v_pages_l: jax.Array,
                        tables: jax.Array, k: jax.Array, v: jax.Array,
                        lens: jax.Array, window: int = 0
                        ) -> Tuple[jax.Array, jax.Array]:
    """Scatter a full prompt (B, S, H, D) into pages for one layer.

    ``tables``: (B, max_pages) physical pages per sequence.  Positions are
    0..S-1 per sequence; tokens past ``lens`` are masked out.
    """
    B, S = k.shape[:2]
    ps = k_pages_l.shape[2]
    pos = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
    logical = pos // ps
    valid = pos < lens[:, None]
    if window > 0:
        ring = -(-window // ps) + 1
        logical = logical % ring
        # ring slots would collide for positions > ring*ps back; only write
        # the live window (deterministic: at most one write per (page, off))
        valid &= pos >= lens[:, None] - ring * ps
    phys = jnp.take_along_axis(tables, logical, axis=1)
    off = pos % ps
    phys = jnp.where(valid, phys, -1)
    return (_scatter_tokens(k_pages_l, phys, off, k),
            _scatter_tokens(v_pages_l, phys, off, v))


def write_layer_prefill_at(k_pages_l: jax.Array, v_pages_l: jax.Array,
                           tables: jax.Array, k: jax.Array, v: jax.Array,
                           start: jax.Array, q_lens: jax.Array,
                           window: int = 0
                           ) -> Tuple[jax.Array, jax.Array]:
    """Scatter a prompt *chunk* (B, C, H, D) into pages for one layer.

    The chunked-prefill generalisation of `write_layer_prefill`: chunk
    token ``i`` lands at absolute position ``start[b] + i``; tokens past
    ``q_lens`` (batch padding) are masked out.  ``start == 0`` and
    ``q_lens == lens`` reproduces the whole-prompt scatter exactly.
    ``window > 0`` wraps the logical page index over the ring; writes
    older than the ring capacity are dropped so at most one write hits
    each (page, offset) slot (deterministic scatter).
    """
    B, C = k.shape[:2]
    ps = k_pages_l.shape[2]
    off_i = jnp.arange(C, dtype=jnp.int32)[None, :]
    pos = start[:, None].astype(jnp.int32) + off_i
    logical = pos // ps
    valid = off_i < q_lens[:, None]
    if window > 0:
        ring = -(-window // ps) + 1
        logical = logical % ring
        end = (start + q_lens)[:, None]
        valid &= pos >= end - ring * ps
    phys = jnp.take_along_axis(tables, jnp.minimum(logical,
                                                   tables.shape[1] - 1),
                               axis=1)
    off = pos % ps
    phys = jnp.where(valid, phys, -1)
    return (_scatter_tokens(k_pages_l, phys, off, k),
            _scatter_tokens(v_pages_l, phys, off, v))


def gather_layer(k_pages_l: jax.Array, v_pages_l: jax.Array,
                 tables: jax.Array, max_len: int
                 ) -> Tuple[jax.Array, jax.Array]:
    """Alg.1 GATHER: materialise (B, max_len, H, D) contiguous K/V.

    Reference path only — the Pallas kernel consumes pages without this copy.
    ``tables``: (B, max_pages).
    """
    ps = k_pages_l.shape[2]
    n_pages = -(-max_len // ps)
    tables = tables[:, :n_pages]  # (B, n_pages)
    safe = jnp.clip(tables, 0, k_pages_l.shape[0] - 1)
    live = jnp.repeat(tables >= 0, ps, axis=1)[:, :, None, None]
    k = jnp.where(live, gather_pages(k_pages_l, safe), 0)
    v = jnp.where(live, gather_pages(v_pages_l, safe), 0)
    return k[:, :max_len], v[:, :max_len]


def copy_page(cache: PagedKVCache, src_page: jax.Array, dst_page: jax.Array
              ) -> PagedKVCache:
    """Copy one physical page across all layers (fork's copy-on-write tail)."""
    src = jnp.clip(src_page, 0, cache.num_pages - 1)
    dst = jnp.where((src_page < 0) | (dst_page < 0), cache.num_pages, dst_page)
    return cache._replace(
        k_pages=cache.k_pages.at[:, dst].set(cache.k_pages[:, src], mode="drop"),
        v_pages=cache.v_pages.at[:, dst].set(cache.v_pages[:, src], mode="drop"),
    )


# ---------------------------------------------------------------------------
# Contiguous (baseline) cache — the paper's comparison target.
# ---------------------------------------------------------------------------
class ContiguousKVCache(NamedTuple):
    """Max-length pre-allocated cache (the fragmenting baseline, §I)."""

    k: jax.Array  # (L, B, max_len, kv_heads, head_dim)
    v: jax.Array
    lens: jax.Array  # (B,)


def init_contiguous(n_layers: int, batch: int, max_len: int, kv_heads: int,
                    head_dim: int, dtype=jnp.float32) -> ContiguousKVCache:
    shape = (n_layers, batch, max_len, kv_heads, head_dim)
    return ContiguousKVCache(
        k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
        lens=jnp.zeros((batch,), jnp.int32),
    )
