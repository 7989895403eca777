"""Pallas TPU kernel: fused paged decode attention (blocked + split-K).

TPU adaptation of the paper's FlexAttention-fused PagedAttention (§III-B).
On GPU the fused kernel gathers scattered KV inside the attention loop
(see the sibling Triton lowering, `paged_attention_gpu.py`, which shares
this module's `decode_partition`, partial contract, and combine); on TPU
random gathers inside a kernel are slow, so the *grid* walks the page
list and the block table is a **scalar-prefetch operand**: the page→HBM
translation happens in the BlockSpec ``index_map``, so the Pallas pipeline's
DMA engine streams exactly the live pages HBM→VMEM, double-buffered, with no
gather materialisation (DESIGN.md §2, A1).

Design (v2: multi-page KV blocks + flash-decoding split-K)
==========================================================

Grid layout
-----------
::

    grid = (batch, kv_heads, num_splits, blocks_per_split)

Each grid step processes one **KV block** of ``pages_per_block`` physical
pages (= ``pages_per_block * page_size`` KV tokens, MXU-aligned when the
product is a multiple of 128).  The split-K axis partitions the page list
into ``num_splits`` contiguous ranges of ``blocks_per_split`` blocks each;
every ``(b, h, s)`` slot runs an independent online softmax over its range
and emits an un-normalised partial ``(m, l, acc)``.  The partials merge
with the numerically-stable flash-decoding correction — the same math
`ref.combine_partials_ref` documents::

    m* = max_s m_s          l* = Σ_s l_s · exp(m_s − m*)
    o  = Σ_s acc_s · exp(m_s − m*) / max(l*, ε)

Two-kernel pipeline & megacore semantics (v3)
---------------------------------------------
The merge runs as the second kernel of a fused two-kernel Pallas
pipeline (``combine_mode="pallas"``, the default whenever split-K is
active): `combine_partials_pallas` walks a ``(batch, kv_head)`` grid and
reduces the whole split axis on-chip per step — max-shift in f32, f32
accumulation, a single output cast — so the partials never round-trip
through an XLA epilogue.  ``combine_mode="jnp"`` keeps the plain jnp
epilogue (`_combine_partials_jnp`); both modes are bit-compatible within
1e-5 and the conformance suite (`tests/test_combine_conformance.py`)
gates them against `ref.combine_partials_ref`.

Both kernels carry ``dimension_semantics``: the decode kernel marks
``(batch, kv_head, split)`` as ``"parallel"`` (the block axis stays
``"arbitrary"`` — its online softmax accumulates in scratch across
steps), and the combine kernel marks ``(batch, kv_head)`` parallel.  On
megacore TPUs Mosaic may therefore place different splits of the *same*
sequence on different cores — the whole point of flash-decoding split-K
for batch=1 long-context decode; without the annotation the grid is
serialised and split-K only ever helped occupancy across batch.
``interpret=None`` auto-resolution (off-TPU ⇒ interpret mode) applies to
both kernels, so the pipeline is testable on CPU CI.

Scattered pages per block
-------------------------
A BlockSpec fetches one contiguous block per operand, so a multi-page block
of *scattered* pages cannot come from a single index_map.  Instead the
k/v pools are passed ``pages_per_block`` times, each copy with its own
index_map reading column ``j`` of the **2-D table slice**
``tables3d[b, s·blocks_per_split + blk, j]``: the pipeline still streams
each scattered page HBM→VMEM as its own (double-buffered) DMA, but the
compute concatenates the ``pages_per_block`` VMEM tiles into one
``(pages_per_block · page_size, head_dim)`` tile so the two matmuls
(``q·Kᵀ`` and ``p·V``) hit the MXU at full width.

Dead entries / ragged lengths
-----------------------------
Table ranks are clamped to the last *live* page of each sequence before
the kernel launches (``min(slot, ceil(len/page) − 1)``): a wholly dead
block therefore indexes the same pages as the previous step, and the
Pallas pipeline skips the re-fetch (a DMA is issued only when an
operand's block index changes between consecutive steps) — pages past
``lens[b]`` are never streamed.  Compute for dead blocks is skipped with
``pl.when``; per-token masking inside a partially-live block uses the
logical position of each page slot.  A fully-empty split emits
``(NEG_INF, 0, 0)`` and drops out of the combine exactly.

VMEM working set per grid step (f32 words unless noted)
-------------------------------------------------------
::

    q        G · D                    (storage dtype)
    k, v     2 · pages_per_block · page_size · D   (storage dtype)
    scores   G · pages_per_block · page_size
    scratch  G · (2 + D)             (m, l, acc — persist across blocks)
    partials G · (2 + D) per (b, h, s) output block

The sliding-window variant masks by ring-slot position (bounded ring
cache, see ``ref.ring_slot_positions``); softcap and int8 ``kv_scale``
dequantisation are applied per block inside the kernel, in both the
blocked and split-K paths.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.errors import EngineConfigError
from repro.kernels import resolve_interpret
# the pure-int partition law lives with the declared launch contracts
# (stdlib-only module) so replint's shape interpreter can load it by path;
# re-exported here — every caller keeps importing it from this module
from repro.kernels.paged_attention.contracts import decode_partition  # noqa: F401

NEG_INF = -1e30

# Megacore grid semantics (single source — the conformance suite asserts
# these).  Decode grid (batch, kv_head, split, block): every (b, h, s)
# slot is an independent online softmax, so the first three axes may run
# on different TPU cores; the block axis accumulates in scratch and must
# stay sequential.  Combine grid (batch, kv_head): fully parallel.
DECODE_DIM_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary")
COMBINE_DIM_SEMANTICS = ("parallel", "parallel")
# Chunked-prefill grid (batch, kv_head, q_block, split, kv_block): every
# (b, h, nq, s) slot is an independent online softmax over its KV range,
# so the first four axes parallelise; the kv-block axis accumulates in
# scratch and stays sequential.
PREFILL_DIM_SEMANTICS = ("parallel", "parallel", "parallel", "parallel",
                         "arbitrary")


COMBINE_MODES = ("jnp", "pallas")


def resolve_combine_mode(mode: Optional[str], num_splits: int) -> str:
    """``None``/"auto" → "pallas" when split-K is active, else "jnp".

    A single split needs no cross-split correction — the jnp epilogue is
    one squeeze + normalise and a kernel launch would be pure overhead.
    Explicit modes pass through (validated).
    """
    if mode is None or mode == "auto":
        return "pallas" if num_splits > 1 else "jnp"
    if mode not in COMBINE_MODES:
        raise EngineConfigError(f"combine_mode must be one of "
                                f"{COMBINE_MODES} or None/'auto', "
                                f"got {mode!r}", combine_mode=mode)
    return mode


def _combine_partials_jnp(m: jax.Array, l: jax.Array, acc: jax.Array,
                          dtype=jnp.float32) -> jax.Array:
    """jnp epilogue combine (the v2 path, kept as oracle-adjacent fallback)."""
    m_g = jnp.max(m, axis=2, keepdims=True)  # (B, Hkv, 1, G)
    corr = jnp.exp(m - m_g)
    l_g = jnp.sum(l * corr, axis=2)  # (B, Hkv, G)
    o = jnp.sum(acc * corr[..., None], axis=2)  # (B, Hkv, G, D)
    return (o / jnp.maximum(l_g, 1e-30)[..., None]).astype(dtype)


def _combine_kernel(m_ref, l_ref, acc_ref, o_ref):
    """Reduce the split axis of one (b, h) slot on-chip.

    Blocks: m/l (1, 1, S, G, 1), acc (1, 1, S, G, D), out (1, 1, G, D).
    Max-shift merge in f32; an all-dead slot (every m == NEG_INF, l == 0)
    yields exact zeros via the ε-clamped denominator.
    """
    m = m_ref[0, 0]  # (S, G, 1) f32
    l = l_ref[0, 0]
    acc = acc_ref[0, 0]  # (S, G, D) f32
    m_g = jnp.max(m, axis=0, keepdims=True)  # (1, G, 1)
    corr = jnp.exp(m - m_g)  # (S, G, 1)
    l_g = jnp.sum(l * corr, axis=0)  # (G, 1)
    o = jnp.sum(acc * corr, axis=0)  # (G, D)
    o_ref[0, 0] = (o / jnp.maximum(l_g, 1e-30)).astype(o_ref.dtype)


def combine_partials_pallas(m: jax.Array, l: jax.Array, acc: jax.Array,
                            dtype=jnp.float32,
                            interpret: Optional[bool] = None) -> jax.Array:
    """Fused split-K combine: one tiny Pallas kernel per (batch, kv_head).

    m, l: (B, Hkv, S, G); acc: (B, Hkv, S, G, D) — f32 (cast if not).
    Returns (B, Hkv, G, D) in ``dtype``.  Both grid axes are marked
    ``"parallel"`` — every (b, h) reduction is independent, so megacore
    TPUs split the grid across cores.  m and l enter the kernel with a
    trailing unit axis, as the partial kernels emit them.
    """
    B, Hkv, S, G = m.shape
    D = acc.shape[-1]
    m = m.astype(jnp.float32)[..., None]
    l = l.astype(jnp.float32)[..., None]
    acc = acc.astype(jnp.float32)
    part_spec = pl.BlockSpec((1, 1, S, G, 1), lambda b, h: (b, h, 0, 0, 0))
    return pl.pallas_call(
        _combine_kernel,
        grid=(B, Hkv),
        in_specs=[
            part_spec,
            part_spec,
            pl.BlockSpec((1, 1, S, G, D), lambda b, h: (b, h, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D), lambda b, h: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=COMBINE_DIM_SEMANTICS),
        interpret=resolve_interpret(interpret),
    )(m, l, acc)


def combine_partials(m: jax.Array, l: jax.Array, acc: jax.Array,
                     dtype=jnp.float32, mode: Optional[str] = None,
                     interpret: Optional[bool] = None) -> jax.Array:
    """Merge split-K partials over the split axis (flash-decoding).

    m, l: (B, Hkv, S, G); acc: (B, Hkv, S, G, D) — all f32.
    Returns (B, Hkv, G, D) in ``dtype``.  ``mode`` picks the fused Pallas
    combine kernel or the jnp epilogue (None → auto by split count).
    """
    mode = resolve_combine_mode(mode, m.shape[2])
    if mode == "pallas":
        return combine_partials_pallas(m, l, acc, dtype=dtype,
                                       interpret=interpret)
    return _combine_partials_jnp(m, l, acc, dtype=dtype)


def _decode_kernel(
    *refs,
    pages_per_block: int,
    blocks_per_split: int,
    scale: float,
    window: int,
    softcap: float,
    kv_scale: float = 0.0,
):
    # positional layout: 2 scalar-prefetch, 1 + 2·ppb inputs, 3 outputs,
    # 3 scratch (see pallas_call below)
    ppb = pages_per_block
    tables_ref, lens_ref, q_ref = refs[0], refs[1], refs[2]
    k_refs = refs[3:3 + ppb]  # each (P, D): one (page, kv head) tile
    v_refs = refs[3 + ppb:3 + 2 * ppb]
    m_out, l_out, acc_out = refs[3 + 2 * ppb:6 + 2 * ppb]
    m_ref, l_ref, acc_ref = refs[6 + 2 * ppb:]

    b = pl.program_id(0)
    s = pl.program_id(2)
    blk = pl.program_id(3)
    page_size = k_refs[0].shape[0]

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    L = lens_ref[b]
    block_rank = s * blocks_per_split + blk  # global KV-block index
    first_page = block_rank * ppb
    # one iota over the whole block: Mosaic cannot concatenate boolean
    # vectors, so the per-token mask is computed in one piece
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, ppb * page_size), 1)

    if window > 0:
        ring = -(-window // page_size) + 1
        cur_page = jnp.maximum(L - 1, 0) // page_size
        pg = first_page + idx // page_size
        # ring slot → logical position (see ref.ring_slot_positions)
        lpage = cur_page - ((cur_page - pg) % ring)
        pos = lpage * page_size + idx % page_size
        pos = jnp.where(pos >= L, pos - ring * page_size, pos)
        live = ((pos >= 0) & (pos < L) & (pos >= L - window)
                & (pg < ring))  # (1, ppb·P)
        block_live = first_page < ring
    else:
        live = first_page * page_size + idx < L  # (1, ppb·P)
        block_live = first_page * page_size < L

    @pl.when(block_live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (G, D)
        k = jnp.concatenate([r[...] for r in k_refs], axis=0)
        v = jnp.concatenate([r[...] for r in v_refs], axis=0)
        k = k.astype(jnp.float32)  # (ppb·P, D)
        v = v.astype(jnp.float32)
        if kv_scale > 0:  # int8 pages: dequantize the VMEM tile in-register
            k = k * kv_scale
            v = v * kv_scale

        s_ = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if softcap > 0:
            s_ = softcap * jnp.tanh(s_ / softcap)
        s_ = jnp.where(live, s_, NEG_INF)  # (G, ppb·P)

        m_prev = m_ref[...]  # (G, 1)
        m_cur = jnp.max(s_, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.where(live, jnp.exp(s_ - m_new), 0.0)

        l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(blk == blocks_per_split - 1)
    def _emit_partial():
        m_out[0, 0, 0] = m_ref[...]
        l_out[0, 0, 0] = l_ref[...]
        acc_out[0, 0, 0] = acc_ref[...]


def _blocked_tables(block_tables: jax.Array, lens: jax.Array, *,
                    num_pages: int, page_size: int, window: int,
                    padded_pages: int, pages_per_block: int) -> jax.Array:
    """(B, max_pages) table → rank-clamped (B, n_blocks, ppb) table slice.

    Dense path: slot ranks are clamped to the last live page of each row,
    so every dead entry repeats an already-streamed page and its DMA is
    elided by the pipeline (same block index as the previous step).
    Windowed path: every ring slot may be live, so only pad-clamp.
    """
    B, max_pages = block_tables.shape
    safe = jnp.clip(block_tables, 0, num_pages - 1).astype(jnp.int32)
    rank = jnp.arange(padded_pages, dtype=jnp.int32)[None, :]
    if window > 0:
        rank = jnp.broadcast_to(jnp.minimum(rank, max_pages - 1),
                                (B, padded_pages))
    else:
        n_live = jnp.maximum(-(-lens // page_size), 1).astype(jnp.int32)
        rank = jnp.minimum(rank, n_live[:, None] - 1)
    flat = jnp.take_along_axis(safe, rank, axis=1)
    return flat.reshape(B, padded_pages // pages_per_block, pages_per_block)


def paged_attention_kernel(
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
    m, l, acc = paged_attention_partials(
        q, k_pages, v_pages, block_tables, lens, scale=scale, window=window,
        softcap=softcap, interpret=interpret, kv_scale=kv_scale,
        pages_per_block=pages_per_block, num_splits=num_splits)
    return combine_partials(m, l, acc, dtype=q.dtype, mode=combine_mode,
                            interpret=interpret)


def paged_attention_partials(
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
    """Split-K partials: ((B,n_kv,S,G) m, (B,n_kv,S,G) l, (B,n_kv,S,G,D) acc).

    The kernel emits m and l with a trailing unit axis, (B, n_kv, S, G, 1),
    so that the last two dims of every output block equal the array's
    (Mosaic's tiling rule for blocks smaller than (8, 128)); the unit axis
    is squeezed here.
    """
    B, n_kv, G, D = q.shape
    num_pages, _, page_size, _ = k_pages.shape
    max_pages = block_tables.shape[1]

    ppb, _, S, bps = decode_partition(max_pages, pages_per_block, num_splits)
    padded_pages = S * bps * ppb

    tables3d = _blocked_tables(
        block_tables, lens, num_pages=num_pages, page_size=page_size,
        window=window, padded_pages=padded_pages, pages_per_block=ppb)

    def q_map(b, h, s, blk, tables, lens):
        return (b, h, 0, 0)

    def part_map(b, h, s, blk, tables, lens):
        return (b, h, s, 0, 0)

    def kv_map(b, h, s, blk, tables, lens, *, j):
        del lens
        return (tables[b, s * bps + blk, j], h, 0, 0)

    # one contiguous (page_size, D) tile per (page, kv head)
    kv_spec = lambda j: pl.BlockSpec((None, None, page_size, D),
                                     functools.partial(kv_map, j=j))

    kernel = functools.partial(
        _decode_kernel, pages_per_block=ppb, blocks_per_split=bps,
        scale=scale, window=window, softcap=softcap, kv_scale=kv_scale)

    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, n_kv, S, bps),
            in_specs=(
                [pl.BlockSpec((1, 1, G, D), q_map)]
                + [kv_spec(j) for j in range(ppb)]       # k pages of a block
                + [kv_spec(j) for j in range(ppb)]       # v pages of a block
            ),
            out_specs=[
                pl.BlockSpec((1, 1, 1, G, 1), part_map),
                pl.BlockSpec((1, 1, 1, G, 1), part_map),
                pl.BlockSpec((1, 1, 1, G, D), part_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, D), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=DECODE_DIM_SEMANTICS),
        out_shape=[
            jax.ShapeDtypeStruct((B, n_kv, S, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, S, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, S, G, D), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(tables3d, lens.astype(jnp.int32), q,
      *([k_pages] * ppb), *([v_pages] * ppb))
    return m[..., 0], l[..., 0], acc


def _prefill_kernel(
    *refs,
    pages_per_block: int,
    blocks_per_split: int,
    q_block: int,
    group: int,
    scale: float,
    softcap: float,
    kv_scale: float = 0.0,
):
    """Chunked-prefill kernel body: one Q-block of ``q_block·G`` rows per
    (b, h, nq, s) slot, online-softmax over its split's KV blocks.

    Positional layout mirrors `_decode_kernel` with one extra scalar
    prefetch (``q_start``) and the q-block grid axis: 3 scalar-prefetch,
    1 + 2·ppb inputs, 3 outputs, 3 scratch.
    """
    ppb = pages_per_block
    tables_ref, lens_ref, qstart_ref = refs[0], refs[1], refs[2]
    q_ref = refs[3]
    k_refs = refs[4:4 + ppb]  # each (P, D): one (page, kv head) tile
    v_refs = refs[4 + ppb:4 + 2 * ppb]
    m_out, l_out, acc_out = refs[4 + 2 * ppb:7 + 2 * ppb]
    m_ref, l_ref, acc_ref = refs[7 + 2 * ppb:]

    b = pl.program_id(0)
    nq = pl.program_id(2)
    s = pl.program_id(3)
    blk = pl.program_id(4)
    page_size = k_refs[0].shape[0]
    R = q_block * group  # rows: r = chunk-token·G + head-group

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    L = lens_ref[b]  # kv_lens: cached tokens incl. the chunk
    q0 = qstart_ref[b]  # absolute position of chunk token 0
    block_rank = s * blocks_per_split + blk
    first_page = block_rank * ppb
    kvpos = first_page * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, ppb * page_size), 1)  # (1, ppb·P)
    row = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
    qpos = q0 + nq * q_block + row // group  # (R, 1) absolute q positions
    # causal upper bound for the whole Q-block: KV blocks wholly past the
    # block's last query never contribute — skip their compute (their DMAs
    # are already elided by the rank clamp in `_blocked_tables`).
    qpos_max = q0 + nq * q_block + q_block - 1
    block_live = (first_page * page_size < L) & \
        (first_page * page_size <= qpos_max)

    @pl.when(block_live)
    def _compute():
        q = q_ref[0, 0, 0].astype(jnp.float32) * scale  # (R, D)
        k = jnp.concatenate([r[...] for r in k_refs], axis=0)
        v = jnp.concatenate([r[...] for r in v_refs], axis=0)
        k = k.astype(jnp.float32)  # (ppb·P, D)
        v = v.astype(jnp.float32)
        if kv_scale > 0:
            k = k * kv_scale
            v = v * kv_scale

        s_ = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if softcap > 0:
            s_ = softcap * jnp.tanh(s_ / softcap)
        live = (kvpos < L) & (kvpos <= qpos)
        s_ = jnp.where(live, s_, NEG_INF)  # (R, ppb·P)

        m_prev = m_ref[...]  # (R, 1)
        m_cur = jnp.max(s_, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.where(live, jnp.exp(s_ - m_new), 0.0)

        l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(blk == blocks_per_split - 1)
    def _emit_partial():
        m_out[0, 0, 0, 0] = m_ref[...]
        l_out[0, 0, 0, 0] = l_ref[...]
        acc_out[0, 0, 0, 0] = acc_ref[...]


def _prefill_q_blocks(q: jax.Array, n_kv: int, q_block: int
                      ) -> Tuple[jax.Array, int]:
    """(B, C, H, D) chunk queries → (B, n_kv, NQ, q_block·G, D) row blocks.

    Row ``r`` of a block is chunk token ``r // G``, head group ``r % G``
    — the layout both prefill lowerings and the partials oracle share.
    """
    B, C, H, D = q.shape
    G = H // n_kv
    nq = -(-C // q_block)
    qpad = jnp.pad(q, ((0, 0), (0, nq * q_block - C), (0, 0), (0, 0)))
    qb = qpad.reshape(B, nq, q_block, n_kv, G, D).transpose(0, 3, 1, 2, 4, 5)
    return qb.reshape(B, n_kv, nq, q_block * G, D), nq


def combine_prefill_partials(m: jax.Array, l: jax.Array, acc: jax.Array,
                             C: int, q_block: int, *, dtype=jnp.float32,
                             mode: Optional[str] = None,
                             interpret: Optional[bool] = None) -> jax.Array:
    """Merge chunked-prefill split-K partials through the *decode* combine.

    m, l: (B, Hkv, NQ, S, R); acc: (B, Hkv, NQ, S, R, D) with
    ``R = q_block·G``.  The q-block axis folds into the batch axis so
    `combine_partials` (jnp epilogue or the fused Pallas kernel) applies
    unchanged — one combine implementation across decode and prefill.
    Returns (B, C, H, D).
    """
    B, n_kv, NQ, S, R = m.shape
    D = acc.shape[-1]
    G = R // q_block
    m2 = m.transpose(0, 2, 1, 3, 4).reshape(B * NQ, n_kv, S, R)
    l2 = l.transpose(0, 2, 1, 3, 4).reshape(B * NQ, n_kv, S, R)
    acc2 = acc.transpose(0, 2, 1, 3, 4, 5).reshape(B * NQ, n_kv, S, R, D)
    o = combine_partials(m2, l2, acc2, dtype=dtype, mode=mode,
                         interpret=interpret)  # (B·NQ, n_kv, R, D)
    o = o.reshape(B, NQ, n_kv, q_block, G, D).transpose(0, 1, 3, 2, 4, 5)
    return o.reshape(B, NQ * q_block, n_kv * G, D)[:, :C]


def paged_prefill_partials(
    q: jax.Array,  # (B, C, n_heads, D) — one prompt chunk per sequence
    k_pages: jax.Array,  # (num_pages, n_kv, P, D)
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, max_pages) int32 (may contain -1)
    kv_lens: jax.Array,  # (B,) cached tokens incl. the chunk
    q_start: jax.Array,  # (B,) absolute position of chunk token 0
    *,
    scale: float,
    softcap: float = 0.0,
    interpret: Optional[bool] = None,
    kv_scale: float = 0.0,
    pages_per_block: int = 1,
    num_splits: int = 1,
    q_block: int = 1,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked-prefill split-K partials (TPU lowering).

    Q-block × cached-KV-block grid: ``(B, n_kv, NQ, num_splits, bps)``,
    sharing `decode_partition`'s page ranges and the decode kernel's
    ``(m, l, acc)`` partial contract with the GQA row axis widened to
    ``q_block·G`` rows.  Returns ((B,Hkv,NQ,S,R) m, (B,Hkv,NQ,S,R) l,
    (B,Hkv,NQ,S,R,D) acc) — f32, shaped for `combine_prefill_partials`
    (m and l leave the kernel with a trailing unit axis, as in decode).
    """
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

    def q_map(b, h, nq, s, blk, tables, lens, qstart):
        return (b, h, nq, 0, 0)

    def part_map(b, h, nq, s, blk, tables, lens, qstart):
        return (b, h, nq, s, 0, 0)

    def kv_map(b, h, nq, s, blk, tables, lens, qstart, *, j):
        del lens, qstart
        return (tables[b, s * bps + blk, j], h, 0, 0)

    kv_spec = lambda j: pl.BlockSpec((None, None, page_size, D),
                                     functools.partial(kv_map, j=j))

    kernel = functools.partial(
        _prefill_kernel, pages_per_block=ppb, blocks_per_split=bps,
        q_block=q_block, group=G, scale=scale, softcap=softcap,
        kv_scale=kv_scale)

    m, l, acc = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, n_kv, NQ, S, bps),
            in_specs=(
                [pl.BlockSpec((1, 1, 1, R, D), q_map)]
                + [kv_spec(j) for j in range(ppb)]
                + [kv_spec(j) for j in range(ppb)]
            ),
            out_specs=[
                pl.BlockSpec((1, 1, 1, 1, R, 1), part_map),
                pl.BlockSpec((1, 1, 1, 1, R, 1), part_map),
                pl.BlockSpec((1, 1, 1, 1, R, D), part_map),
            ],
            scratch_shapes=[
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, 1), jnp.float32),
                pltpu.VMEM((R, D), jnp.float32),
            ],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=PREFILL_DIM_SEMANTICS),
        out_shape=[
            jax.ShapeDtypeStruct((B, n_kv, NQ, S, R, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, NQ, S, R, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n_kv, NQ, S, R, D), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(tables3d, kv_lens.astype(jnp.int32), q_start.astype(jnp.int32), qb5,
      *([k_pages] * ppb), *([v_pages] * ppb))
    return m[..., 0], l[..., 0], acc


def paged_prefill_kernel(
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
    """Full chunked-prefill attention (TPU): partials + shared combine."""
    m, l, acc = paged_prefill_partials(
        q, k_pages, v_pages, block_tables, kv_lens, q_start, scale=scale,
        softcap=softcap, interpret=interpret, kv_scale=kv_scale,
        pages_per_block=pages_per_block, num_splits=num_splits,
        q_block=q_block)
    return combine_prefill_partials(m, l, acc, q.shape[1], q_block,
                                    dtype=q.dtype, mode=combine_mode,
                                    interpret=interpret)


def decode_grid_steps(max_pages: int, *, pages_per_block: int = 1,
                      num_splits: int = 1) -> int:
    """Grid steps per (batch, kv_head) pair — the kernel-launch-overhead
    metric `benchmarks/fig4_decode.py` reports (one-page baseline =
    ``max_pages``)."""
    _, _, S, bps = decode_partition(max_pages, pages_per_block, num_splits)
    return S * bps
