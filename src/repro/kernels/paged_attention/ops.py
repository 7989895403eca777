"""Public op: paged decode attention (kernel or oracle, GQA-aware,
multi-backend).

`paged_attention(...)` is the drop-in attention-over-pages op the rest of
the framework calls.  ``impl="pallas"`` runs the blocked/split-K Pallas
kernel; ``impl="ref"`` runs the pure-jnp oracle (also the dry-run
lowering path — see DESIGN.md §7).

Backends (``backend`` knob; ``None`` → auto from ``jax.default_backend()``,
CPU hosts fall back to the TPU lowering in interpret mode):

  * ``"tpu"`` — `paged_attention.py`: the page→HBM translation happens in
    scalar-prefetched BlockSpec index_maps so Mosaic's pipeline streams
    scattered pages HBM→VMEM, double-buffered; megacore
    ``dimension_semantics`` parallelise (batch, kv_head, split).
  * ``"gpu"`` — `paged_attention_gpu.py`: the Triton lowering
    (``plgpu.CompilerParams``) gathers pages *inside* the kernel
    with block-table indexed ``tl.load``s, one CTA per (batch, kv_head,
    split) grid slot.

Both lowerings share `decode_partition` (bit-identical split ranges),
emit the same ``(m, l, acc)`` partial contract, and merge through the
same `combine_partials` — so `ref.paged_attention_partials_ref` /
`ref.combine_partials_ref` and the conformance suite gate the two
backends identically (interpret mode off-target, compiled on real
hardware; ``interpret=None`` auto-resolves per backend).

``pages_per_block`` / ``num_splits`` control the kernel's KV-block width
and flash-decoding split-K factor; ``combine_mode`` picks the split-K
merge implementation ("pallas" = fused on-chip combine kernel, "jnp" =
XLA epilogue).  ``None`` invokes `choose_decode_params`, the auto-tuning
heuristic keyed on ``(max_pages · page_size, page_size, head_dim)`` and
the backend (MXU-width block targets on TPU, warp-width on GPU), which
also resolves the combine mode (fused kernel whenever split-K is active).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import resolve_backend
from repro.kernels.paged_attention.paged_attention import (
    decode_partition, paged_attention_kernel, paged_prefill_kernel,
    resolve_combine_mode)
from repro.kernels.paged_attention.paged_attention_gpu import (
    paged_attention_kernel_gpu, paged_prefill_kernel_gpu)
from repro.kernels.paged_attention.ref import (paged_attention_ref,
                                               paged_prefill_ref)

# KV tokens per grid step the MXU digests at full width (TPU lowering).
_TARGET_BLOCK_TOKENS = 128
# Per-step K+V VMEM budget (bytes, f32-equivalent) — bounds pages_per_block
# for large head_dim so the double-buffered working set stays comfortable.
_KV_VMEM_BUDGET = 1 << 20
# Flash-decoding split sizing: keep >= this many blocks per split so the
# combine overhead stays negligible, and never exceed _MAX_SPLITS slots.
_MIN_BLOCKS_PER_SPLIT = 4
_MAX_SPLITS = 8

# GPU lowering targets warp-width tiles, not MXU width: a (G, 64) score
# tile keeps two warps of lanes busy per tl.dot step without blowing the
# per-CTA register/SMEM budget the gathered K+V block occupies.
_TARGET_BLOCK_TOKENS_GPU = 64
# K+V bytes per in-flight block (f32-equivalent) — sized to stay well
# inside one SM's shared-memory/register file with double-buffered stages.
_KV_SMEM_BUDGET = 1 << 16
# Split-K is cheaper on GPU (SMs >> TPU cores, combine is one tiny kernel)
# so split earlier and wider: occupancy beats per-split combine overhead.
_MIN_BLOCKS_PER_SPLIT_GPU = 2
_MAX_SPLITS_GPU = 16


def choose_decode_params(
    max_pages: int,
    page_size: int,
    head_dim: int,
    pages_per_block: Optional[int] = None,
    num_splits: Optional[int] = None,
    combine_mode: Optional[str] = None,
    backend: Optional[str] = None,
) -> Tuple[int, int, str]:
    """Auto-tune (pages_per_block, num_splits, combine_mode) per backend.

    Heuristic, keyed on the sequence capacity ``max_pages · page_size``,
    the page size, the head dim, and the target backend:

      * block width targets ``_TARGET_BLOCK_TOKENS`` KV tokens per grid
        step on TPU (MXU-aligned for page sizes ≤ 128) and the smaller
        warp-width ``_TARGET_BLOCK_TOKENS_GPU`` on GPU, capped so the
        K+V block working set stays under the backend's per-step budget
        (VMEM on TPU, SMEM/registers on GPU);
      * split-K grows with the block count (longer sequences → more
        parallel grid slots) but keeps ≥ the backend's minimum blocks
        per split and ≤ its split cap — GPU splits earlier and wider
        (SM occupancy is the scarce resource), short sequences decode
        in a single split with zero combine overhead;
      * the combine runs as the fused Pallas kernel whenever split-K is
        active (> 1 split after clamping) and as the trivial jnp epilogue
        otherwise — a single-split "combine" is just a normalise.  On the
        GPU backend the auto mode resolves to "jnp" even under split-K:
        the fused combine is a TPU lowering, so on a real GPU it would
        fall back to the Pallas *interpreter* on the hot decode path —
        the XLA epilogue is strictly better there (a Triton combine is a
        ROADMAP item).  An explicit ``combine_mode="pallas"`` still
        passes through (that is what the CPU conformance suite runs).

    Explicit values pass through (clamped / validated).
    """
    gpu = resolve_backend(backend) == "gpu"
    target_tokens = _TARGET_BLOCK_TOKENS_GPU if gpu else _TARGET_BLOCK_TOKENS
    kv_budget = _KV_SMEM_BUDGET if gpu else _KV_VMEM_BUDGET
    min_bps = _MIN_BLOCKS_PER_SPLIT_GPU if gpu else _MIN_BLOCKS_PER_SPLIT
    max_splits = _MAX_SPLITS_GPU if gpu else _MAX_SPLITS
    if pages_per_block is None:
        target = max(1, target_tokens // max(1, int(page_size)))
        vmem_cap = max(1, kv_budget // (2 * 4 * int(page_size)
                                        * max(1, int(head_dim))))
        pages_per_block = min(target, vmem_cap)
    # first pass derives n_blocks to *choose* num_splits; the second call
    # below forwards the chosen value
    # replint: disable=knob-threading -- two-phase knob derivation
    ppb, n_blocks, _, _ = decode_partition(max_pages, pages_per_block)
    if num_splits is None:
        num_splits = min(max(1, n_blocks // min_bps), max_splits)
    _, _, ns, _ = decode_partition(max_pages, ppb, num_splits)
    if gpu and combine_mode in (None, "auto"):
        return ppb, ns, "jnp"
    return ppb, ns, resolve_combine_mode(combine_mode, ns)


# Chunked-prefill Q-block sizing: target this many score-tile rows
# (q_block·G) per grid step — MXU-height on TPU; the GPU lowering reuses
# the same target (its CTA walks the KV blocks in-kernel either way).
_TARGET_Q_ROWS = 128


def choose_prefill_params(
    max_pages: int,
    page_size: int,
    head_dim: int,
    chunk: int,
    group: int,
    pages_per_block: Optional[int] = None,
    num_splits: Optional[int] = None,
    combine_mode: Optional[str] = None,
    q_block: Optional[int] = None,
    backend: Optional[str] = None,
) -> Tuple[int, int, str, int]:
    """Auto-tune ``(pages_per_block, num_splits, combine_mode, q_block)``
    for the chunked-prefill kernels.

    KV-block width reuses the decode heuristic (`choose_decode_params`).
    Split-K defaults to **1**: the Q-block axis already multiplies the
    grid by ``ceil(chunk / q_block)``, so extra splits only pay combine
    overhead unless the caller asks for them (the conformance suite
    does).  ``q_block`` targets ``_TARGET_Q_ROWS`` score-tile rows and is
    clamped to the chunk.
    """
    ppb, ns, cm = choose_decode_params(
        max_pages, page_size, head_dim, pages_per_block,
        1 if num_splits is None else num_splits, combine_mode,
        backend=backend)
    if q_block is None:
        q_block = max(1, _TARGET_Q_ROWS // max(1, int(group)))
    q_block = max(1, min(int(q_block), int(chunk)))
    return ppb, ns, cm, q_block


@functools.partial(
    jax.jit,
    static_argnames=("scale", "softcap", "impl", "interpret", "kv_scale",
                     "pages_per_block", "num_splits", "combine_mode",
                     "backend", "q_block"),
)
def paged_prefill(
    q: jax.Array,  # (B, C, n_heads, head_dim) — one prompt chunk per seq
    k_pages: jax.Array,  # (num_pages, n_kv_heads, page_size, head_dim)
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, max_pages)
    kv_lens: jax.Array,  # (B,) cached tokens incl. the chunk
    q_start: jax.Array,  # (B,) absolute position of chunk token 0
    *,
    scale: Optional[float] = None,
    softcap: float = 0.0,
    impl: str = "pallas",
    interpret: Optional[bool] = None,
    kv_scale: float = 0.0,
    pages_per_block: Optional[int] = None,
    num_splits: Optional[int] = None,
    combine_mode: Optional[str] = None,
    backend: Optional[str] = None,  # "tpu" | "gpu" | None → auto
    q_block: Optional[int] = None,  # Q rows per grid step (None → auto)
) -> jax.Array:
    """Chunked paged prefill: ``C`` query tokens per sequence attend
    causally over the sequence's paged KV cache (prefix pages written by
    earlier chunks + the chunk's own causal part, all read through the
    block table).  The write-then-attend counterpart of
    `paged_attention`; see `ref.paged_prefill_ref` for the contract.
    """
    B, C, n_heads, head_dim = q.shape
    n_kv = k_pages.shape[1]
    page_size = k_pages.shape[2]
    max_pages = block_tables.shape[1]
    scale = float(scale if scale is not None else 1.0 / np.sqrt(head_dim))

    if impl == "ref":
        return paged_prefill_ref(
            q, k_pages, v_pages, block_tables, kv_lens, q_start,
            scale=scale, softcap=softcap, kv_scale=kv_scale)

    backend = resolve_backend(backend)
    ppb, ns, cm, qb = choose_prefill_params(
        max_pages, page_size, head_dim, C, n_heads // n_kv,
        pages_per_block, num_splits, combine_mode, q_block, backend=backend)
    kernel = (paged_prefill_kernel_gpu if backend == "gpu"
              else paged_prefill_kernel)
    return kernel(
        q, k_pages, v_pages, block_tables, kv_lens, q_start,
        scale=scale, softcap=softcap, interpret=interpret,
        kv_scale=kv_scale, pages_per_block=ppb, num_splits=ns,
        q_block=qb, combine_mode=cm)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "window", "softcap", "impl", "interpret",
                     "kv_scale", "pages_per_block", "num_splits",
                     "combine_mode", "backend"),
)
def paged_attention(
    q: jax.Array,  # (B, n_heads, head_dim)
    k_pages: jax.Array,  # (num_pages, n_kv_heads, page_size, head_dim)
    v_pages: jax.Array,
    block_tables: jax.Array,  # (B, max_pages)
    lens: jax.Array,  # (B,)
    *,
    scale: Optional[float] = None,
    window: int = 0,
    softcap: float = 0.0,
    impl: str = "pallas",
    interpret: Optional[bool] = None,
    kv_scale: float = 0.0,  # >0: int8 pools, dequantized on the fly
    pages_per_block: Optional[int] = None,  # None → auto-tuned
    num_splits: Optional[int] = None,  # None → auto-tuned
    combine_mode: Optional[str] = None,  # None → auto ("pallas" iff split-K)
    backend: Optional[str] = None,  # "tpu" | "gpu" | None → auto
) -> jax.Array:
    """Attention of one query token per sequence over its paged KV cache."""
    B, n_heads, head_dim = q.shape
    n_kv = k_pages.shape[1]
    page_size = k_pages.shape[2]
    max_pages = block_tables.shape[1]
    scale = float(scale if scale is not None else 1.0 / np.sqrt(head_dim))

    if impl == "ref":
        return paged_attention_ref(
            q, k_pages, v_pages, block_tables, lens,
            scale=scale, window=window, softcap=softcap, kv_scale=kv_scale)

    backend = resolve_backend(backend)
    ppb, ns, cm = choose_decode_params(max_pages, page_size, head_dim,
                                       pages_per_block, num_splits,
                                       combine_mode, backend=backend)
    G = n_heads // n_kv
    qg = q.reshape(B, n_kv, G, head_dim)
    kernel = (paged_attention_kernel_gpu if backend == "gpu"
              else paged_attention_kernel)
    # interpret stays unresolved here: each pallas_call resolves it against
    # its own lowering (the GPU decode kernel interprets iff off-GPU while
    # the shared combine kernel interprets iff off-TPU — on a real GPU the
    # decode compiles through Triton and the combine falls back to the
    # interpreter / jnp epilogue).
    out = kernel(
        qg, k_pages, v_pages, block_tables, lens,
        scale=scale, window=window, softcap=softcap,
        interpret=interpret, kv_scale=kv_scale,
        pages_per_block=ppb, num_splits=ns, combine_mode=cm)
    return out.reshape(B, n_heads, head_dim)
