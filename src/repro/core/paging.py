"""Functional KV page manager — the paper's Algorithm 1, TPU-native.

The paper implements RESERVE / ASSIGN / GATHER with a lock-free free-list in
CUDA global memory.  On TPU we express the same state machine *functionally*:
the manager state is a pytree of fixed-shape device arrays and every
operation is a pure, jit-able function with O(1) work per *page slot*
(vectorised masked pops — no data-dependent shapes, no host sync on the
decode critical path).  A host-side mirror (`HostPageManager`) gives the
serving scheduler true O(1) integer ops for admission control.

Page-pool layout contract (see DESIGN.md §4):
  * physical pages live in pools shaped (num_pages, kv_heads, page_size, hd);
  * under the `tp` decode scheme the page dim is sharded over ("pod","data")
    — each data shard owns a private sub-pool and its slice of the batch;
  * under the `kvp` scheme the page dim is additionally sharded over
    ("model",) and a sequence's pages are striped across model shards
    (block tables are per-shard, shape (B, n_shards, pages_per_shard)).

Prefix sharing: `fork` aliases the shared full pages and bumps refcounts —
the paper's copy-on-write trick; the unshared tail page is freshly allocated
and copied at the cache level.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.errors import SchedulerInvariantError

NULL_PAGE = jnp.int32(-1)


class PageState(NamedTuple):
    """Device-side allocator state (a pytree of fixed-shape arrays)."""

    free_stack: jax.Array  # (num_pages,) int32 — free physical page ids
    free_top: jax.Array  # () int32 — number of free pages on the stack
    refcount: jax.Array  # (num_pages,) int32
    block_tables: jax.Array  # (max_seqs, max_pages) int32, NULL_PAGE = empty
    seq_lens: jax.Array  # (max_seqs,) int32 — tokens stored per sequence

    @property
    def num_pages(self) -> int:
        return self.free_stack.shape[0]

    @property
    def max_pages(self) -> int:
        return self.block_tables.shape[1]


def init_state(num_pages: int, max_seqs: int, max_pages_per_seq: int) -> PageState:
    return PageState(
        free_stack=jnp.arange(num_pages - 1, -1, -1, dtype=jnp.int32),
        free_top=jnp.int32(num_pages),
        refcount=jnp.zeros((num_pages,), jnp.int32),
        block_tables=jnp.full((max_seqs, max_pages_per_seq), NULL_PAGE, jnp.int32),
        seq_lens=jnp.zeros((max_seqs,), jnp.int32),
    )


def pages_needed(n_tokens: jax.Array, page_size: int) -> jax.Array:
    return (n_tokens + page_size - 1) // page_size


def reserve(state: PageState, seq_id: jax.Array, new_len: jax.Array,
            page_size: int) -> PageState:
    """Grow seq ``seq_id``'s reservation to cover ``new_len`` tokens (Alg.1 RESERVE).

    Pops however many pages are needed from the free stack in one vectorised
    masked operation.  If the pool is exhausted the state is returned
    unchanged for the overflowing pages (callers check `has_capacity` first —
    the scheduler's admission-control job, as in the paper's FMS integration).
    """
    row = state.block_tables[seq_id]
    cur_pages = pages_needed(state.seq_lens[seq_id], page_size)
    tgt_pages = pages_needed(new_len, page_size)

    slots = jnp.arange(state.max_pages, dtype=jnp.int32)
    need = (slots >= cur_pages) & (slots < tgt_pages)
    # rank of each needed slot among needed slots: 0,1,2,...
    rank = jnp.cumsum(need.astype(jnp.int32)) - 1
    n_new = jnp.sum(need.astype(jnp.int32))
    avail = jnp.minimum(n_new, state.free_top)
    ok = need & (rank < avail)

    # pop: page for rank r = free_stack[free_top - 1 - r]
    idx = jnp.clip(state.free_top - 1 - rank, 0, state.num_pages - 1)
    popped = state.free_stack[idx]
    new_row = jnp.where(ok, popped, row)

    new_ref = state.refcount.at[jnp.where(ok, popped, 0)].add(
        ok.astype(jnp.int32), mode="drop"
    )
    return state._replace(
        block_tables=state.block_tables.at[seq_id].set(new_row),
        free_top=state.free_top - avail,
        refcount=new_ref,
        seq_lens=state.seq_lens.at[seq_id].set(new_len),
    )


def free(state: PageState, seq_id: jax.Array, page_size: int) -> PageState:
    """Release all pages of ``seq_id`` (Alg.1 implicit FREE path).

    Pages whose refcount drops to zero are pushed back on the free stack;
    shared pages just lose one reference.
    """
    row = state.block_tables[seq_id]
    n_pages = pages_needed(state.seq_lens[seq_id], page_size)
    slots = jnp.arange(state.max_pages, dtype=jnp.int32)
    held = (slots < n_pages) & (row >= 0)

    safe_row = jnp.where(held, row, 0)
    ref_after = state.refcount.at[safe_row].add(-held.astype(jnp.int32), mode="drop")
    releasable = held & (ref_after[safe_row] == 0)

    rank = jnp.cumsum(releasable.astype(jnp.int32)) - 1
    n_rel = jnp.sum(releasable.astype(jnp.int32))
    # route non-releasable slots to an out-of-bounds index (dropped) so they
    # can never collide with a real push at the same stack position
    push_idx = jnp.where(releasable, state.free_top + rank, state.num_pages)
    new_stack = state.free_stack.at[push_idx].set(row, mode="drop")
    return state._replace(
        free_stack=new_stack,
        free_top=state.free_top + n_rel,
        refcount=ref_after,
        block_tables=state.block_tables.at[seq_id].set(
            jnp.full((state.max_pages,), NULL_PAGE)
        ),
        seq_lens=state.seq_lens.at[seq_id].set(0),
    )


def fork(state: PageState, src: jax.Array, dst: jax.Array, page_size: int
         ) -> Tuple[PageState, jax.Array]:
    """Prefix-share: dst aliases src's *full* pages (refcount++), and gets a
    fresh page for the partial tail.  Returns (state, tail_src_page) so the
    cache layer can copy the partial page's K/V data (copy-on-write).

    Capacity guard: callers must check ``has_capacity(state, 1)`` before
    forking a sequence with a partial tail — the vectorised `reserve` has
    no failure channel (it silently leaves the overflowing slot unchanged
    on a dry pool), so an unguarded fork would hand dst a NULL tail page
    while the shared-prefix refcounts were already bumped.  The host
    mirror (`HostPageManager.fork`) enforces the same contract by
    returning ``False`` and rolling the bumps back.
    """
    src_len = state.seq_lens[src]
    full_pages = src_len // page_size
    src_row = state.block_tables[src]

    slots = jnp.arange(state.max_pages, dtype=jnp.int32)
    shared = slots < full_pages
    # bump refcounts on shared pages
    safe = jnp.where(shared, src_row, 0)
    ref = state.refcount.at[safe].add(shared.astype(jnp.int32), mode="drop")
    dst_row = jnp.where(shared, src_row, NULL_PAGE)

    state = state._replace(
        refcount=ref,
        block_tables=state.block_tables.at[dst].set(dst_row),
        seq_lens=state.seq_lens.at[dst].set(full_pages * page_size),
    )
    # fresh tail page (if src had a partial page)
    has_tail = src_len % page_size > 0
    tail_src_page = jnp.where(has_tail, src_row[full_pages], NULL_PAGE)
    state = jax.lax.cond(
        has_tail,
        lambda s: reserve(s, dst, src_len, page_size),
        lambda s: s,
        state,
    )
    return state, tail_src_page


def has_capacity(state: PageState, n_pages: jax.Array) -> jax.Array:
    return state.free_top >= n_pages


def used_pages(state: PageState) -> jax.Array:
    return state.num_pages - state.free_top


def lookup(state: PageState, seq_id: jax.Array, pos: jax.Array, page_size: int
           ) -> Tuple[jax.Array, jax.Array]:
    """logical position -> (physical page, offset)  (Alg.1 lines 7-8)."""
    b = pos // page_size
    o = pos % page_size
    return state.block_tables[seq_id, b], o


# ---------------------------------------------------------------------------
# Host-side mirror: true O(1) integer ops for the scheduler's admission logic.
# ---------------------------------------------------------------------------
class HostPageManager:
    """Python mirror of the allocator for scheduling decisions.

    Interface mirrors Alg. 1; every op is O(pages touched) with O(1)
    amortised pops/pushes (list-based stack).  The device `PageState` remains
    the source of truth for what the kernels read.
    """

    def __init__(self, num_pages: int, page_size: int):
        self.page_size = page_size
        self.num_pages = num_pages
        self.free_list = list(range(num_pages - 1, -1, -1))
        self.refcount = [0] * num_pages
        self.tables: dict[int, list[int]] = {}
        self.lens: dict[int, int] = {}
        # optional global prefix cache (core.prefix_cache.PrefixCache wires
        # itself in here).  Cache residency holds one refcount share per
        # cached page, so `free` *retains* shared-prefix pages (refcount
        # drops to >= 1, page stays off the free list) instead of recycling
        # them, and the invariant generalizes to
        #   refcount[p] == table occurrences of p + (1 if cache-resident)
        self.cache = None

    # -- Alg.1 RESERVE ----------------------------------------------------
    def reserve(self, seq_id: int, new_len: int) -> bool:
        row = self.tables.setdefault(seq_id, [])
        cur = len(row)
        tgt = -(-new_len // self.page_size)
        short = (tgt - cur) - len(self.free_list)
        if short > 0 and self.cache is not None:
            # pool pressure: evict LRU *detached* cached pages back onto
            # the free list before refusing — cached-but-unreferenced
            # pages are reclaimable capacity, not allocation
            self.cache.reclaim(short)
        if tgt - cur > len(self.free_list):
            return False  # admission control: caller must wait / preempt
        for _ in range(tgt - cur):
            p = self.free_list.pop()
            self.refcount[p] += 1
            row.append(p)
        self.lens[seq_id] = new_len
        return True

    def extend(self, seq_id: int, n_tokens: int = 1) -> bool:
        return self.reserve(seq_id, self.lens.get(seq_id, 0) + n_tokens)

    def free(self, seq_id: int) -> None:
        """Release all of ``seq_id``'s pages (refcount--; 0 => back on the
        free list).

        Double-free safe: freeing an unknown rid, or a page whose refcount
        is already zero, raises ``SchedulerInvariantError`` instead of
        silently corrupting the free list (the old behavior pushed the
        page twice, so two later sequences could be handed the same
        physical page — silent KV aliasing with no signal)."""
        if seq_id not in self.tables:
            raise SchedulerInvariantError(
                f"free of unknown rid {seq_id}: no table row — double free "
                "or never-reserved rid", rid=seq_id)
        for p in self.tables.pop(seq_id):
            if self.refcount[p] <= 0:
                raise SchedulerInvariantError(
                    f"double free of page {p} (refcount "
                    f"{self.refcount[p]}) while releasing rid {seq_id}",
                    rid=seq_id, page=p)
            self.refcount[p] -= 1
            if self.refcount[p] == 0:
                self.free_list.append(p)
        self.lens.pop(seq_id, None)

    def fork(self, src: int, dst: int) -> bool:
        """Prefix sharing: dst aliases src's full pages (refcount++) and
        reserves a fresh tail page for src's partial page.

        All-or-nothing: if the pool cannot serve the tail page the shared
        refcount bumps are rolled back and ``False`` is returned — the
        caller must not admit the child.  (Silently keeping the bumps
        while the child has no tail row would let the child decode into a
        never-reserved page and desync refcounts from table occupancy.)

        Forking an unknown/freed ``src`` raises ``SchedulerInvariantError``
        with rid context (like ``free``) — the former bare ``KeyError``
        gave the caller no structured signal that it raced a
        free/preemption of the parent.
        """
        if src not in self.tables or src not in self.lens:
            raise SchedulerInvariantError(
                f"fork from unknown rid {src}: no table row — freed, "
                "preempted, or never reserved", rid=src)
        src_len = self.lens[src]
        full = src_len // self.page_size
        row = self.tables[src][:full]
        for p in row:
            self.refcount[p] += 1
        self.tables[dst] = list(row)
        self.lens[dst] = full * self.page_size
        if src_len % self.page_size:
            if not self.reserve(dst, src_len):
                # dry pool: undo the prefix aliasing entirely
                for p in row:
                    self.refcount[p] -= 1
                del self.tables[dst]
                del self.lens[dst]
                return False
        return True

    def clone(self) -> "HostPageManager":
        """Structural copy for speculative exploration (the replint model
        checker branches the allocator at every transition).  The cache
        hook is *not* carried over — ``PrefixCache.clone`` re-wires it so
        a clone never mutates the original's trie."""
        new = HostPageManager.__new__(HostPageManager)
        new.page_size = self.page_size
        new.num_pages = self.num_pages
        new.free_list = list(self.free_list)
        new.refcount = list(self.refcount)
        new.tables = {rid: list(row) for rid, row in self.tables.items()}
        new.lens = dict(self.lens)
        new.cache = None
        return new

    # -- accounting (paper's <5% overhead metric) -------------------------
    @property
    def used_pages(self) -> int:
        return self.num_pages - len(self.free_list)

    @property
    def available_pages(self) -> int:
        """Pages servable on demand: the free list plus cached pages the
        prefix cache can evict (detached chains).  Capacity checks that
        look only at ``free_list`` under-admit when the cache is warm —
        a full-but-detached cache is reclaimable capacity."""
        n = len(self.free_list)
        if self.cache is not None:
            n += self.cache.reclaimable()
        return n

    def bytes_reserved(self, kv_heads: int, head_dim: int, n_layers: int,
                       itemsize: int = 2) -> int:
        per_page = self.page_size * kv_heads * head_dim * 2 * n_layers * itemsize
        return self.used_pages * per_page

    def bytes_theoretical_min(self, kv_heads: int, head_dim: int, n_layers: int,
                              itemsize: int = 2) -> int:
        tokens = sum(self.lens.values())
        return tokens * kv_heads * head_dim * 2 * n_layers * itemsize

    def overhead_frac(self, kv_heads: int = 1, head_dim: int = 1,
                      n_layers: int = 1) -> float:
        mn = self.bytes_theoretical_min(kv_heads, head_dim, n_layers)
        if mn == 0:
            return 0.0
        return self.bytes_reserved(kv_heads, head_dim, n_layers) / mn - 1.0
