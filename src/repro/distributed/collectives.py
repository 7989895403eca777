"""Distributed paged-decode attention + cache writes (shard_map wrappers).

GSPMD cannot know that block-table gathers are shard-local, so the paged
pools + tables enter explicit ``shard_map`` regions here.  Three schemes
(DESIGN.md §4):

  * ``tp``  — vLLM-faithful tensor parallelism: batch over (pod, data),
    q *and* kv heads over "model" (requires n_kv_heads % model == 0);
    page pools private per data shard.
  * ``dp``  — for *windowed* (bounded-ring) layers: pool sharded over the
    batch axes only, kv replicated over "model", q-head-groups over
    "model".  The ring is small, so replication beats striping.
  * ``kvp`` — flash-decoding on the mesh (beyond-paper): the page dim is
    round-robin *striped* over every mesh axis not used for batch; each
    shard computes a partial online-softmax over its local pages and the
    partials merge with the numerically-stable (m, l, o) combine
    (`merge_flash_partials` — by default the same fused Pallas combine
    kernel the single-device split-K decode uses, with a pmax/psum
    fallback under ``combine_mode="jnp"``).  Works for any GQA layout and
    is what makes batch=1 × 524k-token decode shardable at all.

Table layout contract: tables are (B, n_kv_shards, pages_per_shard); under
``kvp`` local slot j of kv-shard s holds logical page j·n_kv_shards + s.
Under ``tp``/``dp``/local, n_kv_shards == 1 and slots are logical pages.

Outside a mesh context every wrapper is a plain local call (CPU engine).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map as _shard_map


def shard_map(f, mesh, in_specs, out_specs, check_rep=False):
    return _shard_map(f, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs, check_vma=check_rep)

from repro.core import attention as core_attn
from repro.core import cache as kvcache
from repro.distributed.sharding import axis_size, current_mesh


def _flat_axis_index(axes: Tuple[str, ...]) -> jax.Array:
    idx = jnp.int32(0)
    for a in axes:
        # axis_size resolves statically from the mesh context;
        # axis_index is per-shard as usual
        idx = idx * axis_size(a) + jax.lax.axis_index(a)
    return idx


def _mesh_prod(mesh, axes: Tuple[str, ...]) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return math.prod(sizes[a] for a in axes) if axes else 1


def merge_flash_partials(
    m: jax.Array,  # (B, H) f32 — per-shard running max (NEG_INF if dead)
    l: jax.Array,  # (B, H) f32 — per-shard softmax mass
    o: jax.Array,  # (B, H, D) f32 — per-shard un-normalised accumulator
    axes: Tuple[str, ...],
    *,
    combine_mode: Optional[str] = None,
    out_dtype=jnp.float32,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Merge per-shard flash-decoding partials over mesh ``axes``.

    Runs *inside* shard_map (the kvp decode path).  ``combine_mode``
    selects the reduction implementation:

      * ``"pallas"`` — all-gather the shard axis into a split axis and
        reduce it with the *same* fused combine kernel the single-device
        split-K pipeline uses (`combine_partials_pallas`, each head its
        own (b, h) grid slot) — one reduction implementation across the
        local and distributed paths;
      * ``"jnp"`` — the two-pass pmax/psum merge (no gather; partials
        stay shard-resident).

    ``None`` → auto: pallas when more than one shard participates.
    Returns (B, H, D) in ``out_dtype``.
    """
    from repro.kernels.paged_attention.paged_attention import (
        combine_partials_pallas, resolve_combine_mode)

    n_sh = math.prod(axis_size(a) for a in axes) if axes else 1
    mode = resolve_combine_mode(combine_mode, n_sh)
    if mode == "pallas":
        B, H = m.shape
        D = o.shape[-1]
        ms = jax.lax.all_gather(m, axes)  # (n_sh, B, H)
        ls = jax.lax.all_gather(l, axes)
        os_ = jax.lax.all_gather(o, axes)  # (n_sh, B, H, D)
        m4 = ms.transpose(1, 2, 0)[..., None]  # (B, H, S, 1) — G = 1
        l4 = ls.transpose(1, 2, 0)[..., None]
        acc5 = os_.transpose(1, 2, 0, 3)[:, :, :, None, :]  # (B, H, S, 1, D)
        out = combine_partials_pallas(m4, l4, acc5, dtype=out_dtype,
                                      interpret=interpret)
        return out.reshape(B, H, D)
    m_g = jax.lax.pmax(m, axes)
    corr = jnp.exp(m - m_g)
    l_g = jax.lax.psum(l * corr, axes)
    o_g = jax.lax.psum(o * corr[..., None], axes)
    return (o_g / jnp.maximum(l_g, 1e-30)[..., None]).astype(out_dtype)


def decode_attention_sharded(
    q4: jax.Array,  # (B, Hkv, G, hd) — q heads grouped by kv head
    k_pages: jax.Array,  # (num_pages, Hkv, P, hd)
    v_pages: jax.Array,
    tables: jax.Array,  # (B, n_kv_shards, pages_per_shard) int32
    lens: jax.Array,  # (B,)
    *,
    window: int = 0,
    softcap: float = 0.0,
    scheme: str = "local",  # local | tp | dp | kvp
    batch_axes: Tuple[str, ...] = (),
    impl: str = "ref",
    interpret: Optional[bool] = None,
    kv_scale: float = 0.0,  # >0: int8 pools with this dequant step
    pages_per_block: Optional[int] = None,  # Pallas KV-block width (None=auto)
    num_splits: Optional[int] = None,  # Pallas split-K factor (None=auto)
    combine_mode: Optional[str] = None,  # split-K merge impl (None=auto)
    backend: Optional[str] = None,  # kernel lowering: tpu | gpu (None=auto)
) -> jax.Array:
    """Returns (B, Hkv, G, hd)."""
    mesh = current_mesh()

    def _local(q4, k_pages, v_pages, tables, lens, kv_psum_axes=(),
               page_stride=1, page_offset=0):
        b, nk, g, d = q4.shape
        q = q4.reshape(b, nk * g, d)
        t = tables.reshape(b, -1)
        o = core_attn.decode_attention(
            q, k_pages, v_pages, t, lens, window=window, softcap=softcap,
            impl=impl, kv_psum_axes=kv_psum_axes, page_stride=page_stride,
            page_offset=page_offset, interpret=interpret, kv_scale=kv_scale,
            pages_per_block=pages_per_block, num_splits=num_splits,
            combine_mode=combine_mode, backend=backend)
        return o.reshape(b, nk, g, d)

    if mesh is None or scheme == "local":
        return _local(q4, k_pages, v_pages, tables, lens)

    ba = tuple(batch_axes) or None

    if scheme == "tp":
        in_specs = (P(ba, "model", None, None),
                    P(ba, "model", None, None), P(ba, "model", None, None),
                    P(ba, None, None), P(ba))
        fn = shard_map(_local, mesh=mesh, in_specs=in_specs,
                       out_specs=P(ba, "model", None, None), check_rep=False)
        return fn(q4, k_pages, v_pages, tables, lens)

    if scheme == "dp":
        # shard q-head groups over "model" when divisible; otherwise the
        # bounded-window attention is cheap enough to replicate (e.g.
        # nemotron-15b's G=6 on a 16-wide model axis)
        msize = _mesh_prod(mesh, ("model",)) if "model" in mesh.axis_names else 1
        g_ax = "model" if q4.shape[2] % max(msize, 1) == 0 else None
        in_specs = (P(ba, None, g_ax, None),
                    P(ba, None, None, None), P(ba, None, None, None),
                    P(ba, None, None), P(ba))
        fn = shard_map(_local, mesh=mesh, in_specs=in_specs,
                       out_specs=P(ba, None, g_ax, None), check_rep=False)
        return fn(q4, k_pages, v_pages, tables, lens)

    # ---- kvp ---------------------------------------------------------------
    kv_axes = tuple(a for a in mesh.axis_names if a not in (batch_axes or ()))
    n_kv = _mesh_prod(mesh, kv_axes)
    page_axes = tuple(batch_axes) + kv_axes

    def _kvp(q4, k_pages, v_pages, tables, lens):
        return _local(q4, k_pages, v_pages, tables, lens,
                      kv_psum_axes=kv_axes, page_stride=n_kv,
                      page_offset=_flat_axis_index(kv_axes))

    in_specs = (P(ba, None, None, None),
                P(page_axes, None, None, None), P(page_axes, None, None, None),
                P(ba, kv_axes, None), P(ba))
    fn = shard_map(_kvp, mesh=mesh, in_specs=in_specs,
                   out_specs=P(ba, None, None, None), check_rep=False)
    return fn(q4, k_pages, v_pages, tables, lens)


def write_prefill_sharded(
    k_pages_l: jax.Array,  # (num_pages, Hkv, P, hd)
    v_pages_l: jax.Array,
    tables: jax.Array,  # (B, max_pages) — pool-shard-local physical ids
    k: jax.Array,  # (B, S, Hkv, hd)
    v: jax.Array,
    lens: jax.Array,
    *,
    window: int = 0,
) -> Tuple[jax.Array, jax.Array]:
    """Scatter a prompt's K/V into the paged pools, shard-locally.

    Under GSPMD the pool scatter all-gathers every update row to every
    device (measured 8 GiB/device/layer on 32k prefill — the dominant
    prefill collective).  Here the pools are sharded (pages × batch-axes,
    head_dim × "model") and each shard scatters only its local rows: the
    only collective left is the reshard of k/v into that layout (an
    all-to-all of one KV slice).  Decode's kvp layout differs (pages
    striped over "model"); the prefill→decode pool reshard is the
    disaggregated-serving phase boundary (DESIGN.md §4).
    """
    mesh = current_mesh()
    if mesh is None:
        return kvcache.write_layer_prefill(k_pages_l, v_pages_l, tables,
                                           k, v, lens, window=window)
    from repro.distributed.sharding import current_rules
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    ba = tuple(a for a in (current_rules().physical("batch") or ())
               if a in sizes and k.shape[0] % sizes[a] == 0)
    hd_ax = ("model" if "model" in sizes
             and k.shape[-1] % sizes["model"] == 0 else None)
    ba_s = ba or None

    def _local(kp, vp, tbl, k, v, lens):
        return kvcache.write_layer_prefill(kp, vp, tbl, k, v, lens,
                                           window=window)

    fn = shard_map(
        _local, mesh,
        in_specs=(P(ba_s, None, None, hd_ax), P(ba_s, None, None, hd_ax),
                  P(ba_s, None), P(ba_s, None, None, hd_ax),
                  P(ba_s, None, None, hd_ax), P(ba_s)),
        out_specs=(P(ba_s, None, None, hd_ax), P(ba_s, None, None, hd_ax)),
        check_rep=False)
    return fn(k_pages_l, v_pages_l, tables, k, v, lens)


def write_decode_sharded(
    k_pages: jax.Array,  # (num_pages, Hkv, P, hd)
    v_pages: jax.Array,
    tables: jax.Array,  # (B, n_kv_shards, pages_per_shard)
    positions: jax.Array,  # (B,) — 0-based position of the incoming token
    k_new: jax.Array,  # (B, Hkv, hd)
    v_new: jax.Array,
    *,
    window: int = 0,
    scheme: str = "local",
    batch_axes: Tuple[str, ...] = (),
) -> Tuple[jax.Array, jax.Array]:
    """Scatter one new token per sequence into the (sharded) pools."""
    mesh = current_mesh()
    page_size = k_pages.shape[2]

    def _scatter(kp, vp, phys, off, k, v):
        oob = jnp.where(phys < 0, kp.shape[0], phys)
        return (kp.at[oob, :, off].set(k, mode="drop"),
                vp.at[oob, :, off].set(v, mode="drop"))

    def _local(kp, vp, tbl, pos, k, v, stride=1, offset=0):
        logical = pos // page_size
        if window > 0:
            ring = -(-window // page_size) + 1
            logical = logical % ring
        if stride == 1:
            slot = logical
            mine = jnp.ones_like(pos, dtype=bool)
        else:
            slot = logical // stride
            mine = (logical % stride) == offset
        t = tbl.reshape(tbl.shape[0], -1)
        phys = jnp.where(mine, jnp.take_along_axis(
            t, slot[:, None], axis=1)[:, 0], -1)
        return _scatter(kp, vp, phys, pos % page_size, k, v)

    if mesh is None or scheme == "local":
        return _local(k_pages, v_pages, tables, positions, k_new, v_new)

    ba = tuple(batch_axes) or None

    if scheme == "tp":
        in_specs = (P(ba, "model", None, None), P(ba, "model", None, None),
                    P(ba, None, None), P(ba),
                    P(ba, "model", None), P(ba, "model", None))
        out_specs = (P(ba, "model", None, None), P(ba, "model", None, None))
        fn = shard_map(_local, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_rep=False)
        return fn(k_pages, v_pages, tables, positions, k_new, v_new)

    if scheme == "dp":
        in_specs = (P(ba, None, None, None), P(ba, None, None, None),
                    P(ba, None, None), P(ba),
                    P(ba, None, None), P(ba, None, None))
        out_specs = (P(ba, None, None, None), P(ba, None, None, None))
        fn = shard_map(_local, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_rep=False)
        return fn(k_pages, v_pages, tables, positions, k_new, v_new)

    # kvp: only the owning stripe shard commits the write
    kv_axes = tuple(a for a in mesh.axis_names if a not in (batch_axes or ()))
    n_kv = _mesh_prod(mesh, kv_axes)
    page_axes = tuple(batch_axes) + kv_axes

    def _kvp(kp, vp, tbl, pos, k, v):
        return _local(kp, vp, tbl, pos, k, v, stride=n_kv,
                      offset=_flat_axis_index(kv_axes))

    in_specs = (P(page_axes, None, None, None), P(page_axes, None, None, None),
                P(ba, kv_axes, None), P(ba),
                P(ba, None, None), P(ba, None, None))
    out_specs = (P(page_axes, None, None, None),
                 P(page_axes, None, None, None))
    fn = shard_map(_kvp, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                   check_rep=False)
    return fn(k_pages, v_pages, tables, positions, k_new, v_new)
