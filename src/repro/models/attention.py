"""GQA attention layer: projections + RoPE around the core attention ops.

Three phases share the same parameters:
  train   — full-sequence causal (optionally windowed) attention;
  prefill — same, but also scatters K/V into the paged cache;
  decode  — one token via the paged kernel (or the contiguous baseline).

Cross-attention (VLM image layers, whisper enc→dec) reuses the projections
with externally-provided K/V and no causal mask.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import attention as core_attn
from repro.core import cache as kvcache
from repro.core.paging import PageState
from repro.distributed.sharding import logical_shard
from repro.models.layers import apply_rope
from repro.models.spec import ParamSpec


def attn_spec(cfg: ModelConfig) -> Dict:
    d, H, Hkv, hd = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                     cfg.resolved_head_dim)
    return {
        "wq": ParamSpec((d, H, hd), ("embed", "heads", None)),
        "wk": ParamSpec((d, Hkv, hd), ("embed", "kv_heads", None)),
        "wv": ParamSpec((d, Hkv, hd), ("embed", "kv_heads", None)),
        "wo": ParamSpec((H, hd, d), ("heads", None, "embed")),
    }


def _qkv(p: Dict, x: jax.Array, positions: Optional[jax.Array],
         theta: float) -> Tuple[jax.Array, jax.Array, jax.Array]:
    q = jnp.einsum("...d,dhk->...hk", x, p["wq"])
    k = jnp.einsum("...d,dhk->...hk", x, p["wk"])
    v = jnp.einsum("...d,dhk->...hk", x, p["wv"])
    if positions is not None:
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    # seq dim annotated "attn_seq": None in the TP plan (heads carry
    # "model"), ("model",) under the ring plan (heads replicated) — without
    # it the constraint would force an all-gather of q/k/v over "model"
    # right before ring attention re-shards them (measured 1.5 GiB/layer)
    lead = ("attn_seq",) * (x.ndim - 2)
    q = logical_shard(q, "batch", *lead, "heads", None)
    k = logical_shard(k, "batch", *lead, "kv_heads", None)
    v = logical_shard(v, "batch", *lead, "kv_heads", None)
    return q, k, v


def kv_quant(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    """Quantize K/V for pool storage (int8 mode); identity otherwise."""
    if cfg.kv_dtype != "int8":
        return x
    q = jnp.round(x.astype(jnp.float32) / cfg.kv_scale)
    return jnp.clip(q, -127, 127).astype(jnp.int8)


def kv_pool_dtype(cfg: ModelConfig, dtype):
    return jnp.int8 if cfg.kv_dtype == "int8" else dtype


def _out(p: Dict, o: jax.Array) -> jax.Array:
    y = jnp.einsum("...hk,hkd->...d", o, p["wo"])
    seq = ("seq",) if y.ndim == 3 else ()
    return logical_shard(y, "batch", *seq, "act_embed")


# ---------------------------------------------------------------------------
def attn_train(p: Dict, x: jax.Array, cfg: ModelConfig, *, window: int = 0,
               lens: Optional[jax.Array] = None, causal: bool = True,
               impl: str = "jnp") -> jax.Array:
    """x: (B, S, d) → (B, S, d)."""
    B, S, _ = x.shape
    pos = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
    q, k, v = _qkv(p, x, pos, cfg.rope_theta)
    o = core_attn.prefill_attention(q, k, v, window=window, lens=lens,
                                    causal=causal, impl=impl)
    return _out(p, o)


def attn_prefill(p: Dict, x: jax.Array, cfg: ModelConfig,
                 k_pages: jax.Array, v_pages: jax.Array, tables: jax.Array,
                 lens: jax.Array, *, window: int = 0, impl: str = "jnp"
                 ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Prefill: attend over the prompt AND write K/V into the paged pools.

    ``tables``: (B, n_kv_shards, pages_per_shard) — prefill pools are laid
    out per-data-shard (n_kv_shards == 1); a disaggregated deployment
    reshards pools between prefill and decode engines (DESIGN.md §4).

    Returns (out, k_pages', v_pages').
    """
    B, S, _ = x.shape
    pos = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
    q, k, v = _qkv(p, x, pos, cfg.rope_theta)
    from repro.distributed.collectives import write_prefill_sharded
    k_pages, v_pages = write_prefill_sharded(
        k_pages, v_pages, tables.reshape(B, -1), kv_quant(cfg, k),
        kv_quant(cfg, v), lens, window=window)
    o = core_attn.prefill_attention(q, k, v, window=window, lens=lens,
                                    impl=impl)
    return _out(p, o), k_pages, v_pages


def attn_prefill_chunked(p: Dict, x: jax.Array, cfg: ModelConfig,
                         k_pages: jax.Array, v_pages: jax.Array,
                         tables: jax.Array, q_start: jax.Array,
                         q_lens: jax.Array, *, window: int = 0,
                         impl: str = "jnp",
                         interpret: Optional[bool] = None,
                         pages_per_block: Optional[int] = None,
                         num_splits: Optional[int] = None,
                         combine_mode: Optional[str] = None,
                         backend: Optional[str] = None,
                         ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked prefill: attend one prompt *chunk* resuming from the cached
    prefix, writing the chunk's K/V into the existing pages.

    ``x``: (B, C, d) chunk activations; ``q_start``: (B,) tokens already
    cached (the resume position — RoPE and masks use absolute positions
    ``q_start + i``); ``q_lens``: (B,) live tokens of this chunk (≤ C,
    batch padding beyond).  ``tables``: (B, n_kv_shards, pages_per_shard)
    with the rows the scheduler reserved chunk-by-chunk.

    Dense layers follow the decode contract — scatter first, then the
    prefix-aware paged attention reads prefix *and* chunk back through
    the block table (`core_attn.prefill_attention_paged`; ``impl=
    "pallas"`` runs the Q-block × KV-block kernel).  Sliding-window
    layers attend first over the intact ring prefix + fresh chunk K/V,
    then scatter (ring wraps would otherwise overwrite prefix slots the
    chunk still needs).

    Returns (out, k_pages', v_pages').
    """
    B, C, _ = x.shape
    pos = (q_start[:, None].astype(jnp.int32)
           + jnp.arange(C, dtype=jnp.int32)[None])
    q, k, v = _qkv(p, x, pos, cfg.rope_theta)
    kv_scale = cfg.kv_scale if cfg.kv_dtype == "int8" else 0.0
    t = tables.reshape(B, -1)
    if window > 0:
        o = core_attn.prefill_attention_windowed_chunk(
            q, k, v, k_pages, v_pages, t, q_start, q_lens,
            window=window, kv_scale=kv_scale)
        k_pages, v_pages = kvcache.write_layer_prefill_at(
            k_pages, v_pages, t, kv_quant(cfg, k), kv_quant(cfg, v),
            q_start, q_lens, window=window)
    else:
        k_pages, v_pages = kvcache.write_layer_prefill_at(
            k_pages, v_pages, t, kv_quant(cfg, k), kv_quant(cfg, v),
            q_start, q_lens)
        o = core_attn.prefill_attention_paged(
            q, k_pages, v_pages, t, q_start + q_lens, q_start,
            impl=impl, interpret=interpret, kv_scale=kv_scale,
            pages_per_block=pages_per_block, num_splits=num_splits,
            combine_mode=combine_mode, backend=backend)
    return _out(p, o), k_pages, v_pages


def attn_decode(p: Dict, x: jax.Array, cfg: ModelConfig,
                k_pages: jax.Array, v_pages: jax.Array, tables: jax.Array,
                positions: jax.Array, *, window: int = 0,
                impl: str = "ref", attn_ctx: Optional[Dict] = None,
                interpret: Optional[bool] = None,
                pages_per_block: Optional[int] = None,
                num_splits: Optional[int] = None,
                combine_mode: Optional[str] = None,
                backend: Optional[str] = None,
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Decode one token.  x: (B, d); positions: (B,) 0-based position of the
    incoming token; tables: (B, n_kv_shards, pages_per_shard).  Appends K/V
    then attends over lens = positions+1 tokens.

    ``attn_ctx`` = {"scheme": local|tp|dp|kvp, "batch_axes": (...)} selects
    the distribution scheme (DESIGN.md §4); windowed layers degrade kvp→dp
    (bounded ring pools are replicated across "model", not striped).
    ``pages_per_block`` / ``num_splits`` tune the Pallas decode kernel's
    KV-block width and flash-decoding split-K factor; ``combine_mode``
    picks the split-K merge implementation, local and distributed alike
    ("pallas" = fused combine kernel, "jnp" = epilogue; None → auto);
    ``backend`` selects the kernel lowering ("tpu" | "gpu"; None → auto
    from the running platform).

    Returns (out, k_pages', v_pages').
    """
    from repro.distributed.collectives import (
        decode_attention_sharded, write_decode_sharded)

    ctx = attn_ctx or {}
    scheme = ctx.get("scheme", "local")
    if window > 0 and scheme == "kvp":
        scheme = "dp"
    batch_axes = tuple(ctx.get("batch_axes", ()))

    B = x.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q, k, v = _qkv(p, x, positions, cfg.rope_theta)  # (B, H/Hkv, hd)
    k_pages, v_pages = write_decode_sharded(
        k_pages, v_pages, tables, positions, kv_quant(cfg, k),
        kv_quant(cfg, v), window=window,
        scheme=scheme, batch_axes=batch_axes)
    q4 = q.reshape(B, Hkv, H // Hkv, hd)
    o4 = decode_attention_sharded(
        q4, k_pages, v_pages, tables, positions + 1, window=window,
        scheme=scheme, batch_axes=batch_axes, impl=impl, interpret=interpret,
        kv_scale=cfg.kv_scale if cfg.kv_dtype == "int8" else 0.0,
        pages_per_block=pages_per_block, num_splits=num_splits,
        combine_mode=combine_mode, backend=backend)
    return _out(p, o4.reshape(B, H, hd)), k_pages, v_pages


def attn_decode_contiguous(p: Dict, x: jax.Array, cfg: ModelConfig,
                           k_buf: jax.Array, v_buf: jax.Array,
                           positions: jax.Array, *, window: int = 0
                           ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The paper's baseline path: max-length contiguous per-request buffers.

    k_buf/v_buf: (B, max_len, Hkv, hd).
    """
    q, k, v = _qkv(p, x, positions, cfg.rope_theta)
    B = x.shape[0]
    k_buf = k_buf.at[jnp.arange(B), positions].set(k)
    v_buf = v_buf.at[jnp.arange(B), positions].set(v)
    o = core_attn.decode_attention_contiguous(
        q, k_buf, v_buf, positions + 1, window=window)
    return _out(p, o), k_buf, v_buf


def cross_attn(p: Dict, x: jax.Array, k: jax.Array, v: jax.Array,
               cfg: ModelConfig) -> jax.Array:
    """Cross attention: q from x (B, S, d) or (B, d); k/v precomputed
    (B, T, Hkv, hd).  No positional rotation (keys carry none)."""
    q = jnp.einsum("...d,dhk->...hk", x, p["wq"])
    squeeze = x.ndim == 2
    if squeeze:
        q = q[:, None]
    o = core_attn.prefill_attention(q, k, v, causal=False, impl="jnp")
    if squeeze:
        o = o[:, 0]
    return _out(p, o)


def cross_kv(p: Dict, ctx: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Precompute cross-attention K/V from encoder/image context (B, T, d)."""
    k = jnp.einsum("btd,dhk->bthk", ctx, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", ctx, p["wv"])
    return k, v


def cross_gate(q_start) -> Dict:
    """Which rows of a prompt chunk compute fresh cross-attention K/V.

    Only rows at chunk 0 (``q_start == 0``, host ints) need them; resume
    rows reuse their cached rows.  Returns the keywords ``prefill_chunk``
    takes: ``cross_mode`` "full" (every row is at chunk 0), "reuse" (none
    is) or "partial", with ``first_rows`` the indices of those that are.
    Decided on the host, so a jitted chunk step stays free of host syncs.
    """
    firsts = np.flatnonzero(np.asarray(q_start) == 0).astype(np.int32)
    if firsts.size == len(q_start):
        return {"cross_mode": "full"}
    if firsts.size == 0:
        return {"cross_mode": "reuse"}
    return {"cross_mode": "partial", "first_rows": firsts}
