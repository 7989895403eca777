"""Paper Fig. 4: steady-state decode latency (per token) across sequence
lengths — PagedAttention vs the default (contiguous max-length) kernel.

Both paths run the identical model; only the KV layout + attention op
differ.  The paper reports paged consistently at-or-below the default with
near-linear scaling.

Also reports the Pallas kernel's grid economics (fixed page_size=16, the
paper's decode page size): ``grid_1p`` is the one-page-per-step baseline
(= max_pages steps per (batch, kv_head) pair), ``grid_blk`` the blocked +
split-K kernel with auto-tuned ``(pages_per_block, num_splits)``, and
``grid_x`` the reduction factor — ≥4× at seq 2048 is the kernel-overhead
win the blocked rewrite targets.  ``pallas_us`` times the real kernel in
interpret mode (CPU): it measures *semantics*, not TPU speed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmarks.common import Table, timeit
from repro.configs import get_smoke
from repro.configs.base import RunConfig
from repro.core.attention import (choose_decode_params, decode_attention,
                                  decode_attention_contiguous)
from repro.kernels.paged_attention.paged_attention import decode_grid_steps

SEQ_LENS = [128, 256, 512, 1024, 2048]
PAGE_SIZE = 16  # the paper's decode page size (fixed for comparability)


def run(fast: bool = False, backend: str = None):
    cfg = get_smoke("llama2-7b")
    H, Hkv, D = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ps = PAGE_SIZE
    B = 4
    seq_lens = SEQ_LENS[:3] if fast else SEQ_LENS
    t = Table("fig4_decode",
              ["seq_len", "backend", "paged_us", "contiguous_us",
               "paged/contig", "pallas_us", "ppb", "splits", "grid_blk",
               "grid_1p", "grid_x"])

    paged = jax.jit(lambda q, kp, vp, bt, l: decode_attention(
        q, kp, vp, bt, l, impl="ref"))
    # the kernel axis honours --backend (TPU scalar-prefetch pipeline or
    # GPU/Triton in-kernel gather; None → auto from the platform)
    pallas = jax.jit(lambda q, kp, vp, bt, l: decode_attention(
        q, kp, vp, bt, l, impl="pallas", backend=backend))
    contig = jax.jit(decode_attention_contiguous)
    bk = backend or "auto"

    for S in SEQ_LENS:
        mp = -(-S // ps)
        # grid accounting is free — report it for every seq_len, even the
        # ones --fast skips timing for
        ppb, ns, _ = choose_decode_params(mp, ps, D, backend=backend)
        g1 = decode_grid_steps(mp)
        gb = decode_grid_steps(mp, pages_per_block=ppb, num_splits=ns)
        gx = round(g1 / gb, 2)
        if S not in seq_lens:
            t.add(S, bk, "-", "-", "-", "-", ppb, ns, gb, g1, gx)
            continue

        ks = jax.random.split(jax.random.PRNGKey(S), 5)
        q = jax.random.normal(ks[0], (B, H, D))
        kp = jax.random.normal(ks[1], (B * mp, Hkv, ps, D))
        vp = jax.random.normal(ks[2], (B * mp, Hkv, ps, D))
        bt = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
        lens = jnp.full((B,), S, jnp.int32)
        kc = jax.random.normal(ks[3], (B, S, Hkv, D))
        vc = jax.random.normal(ks[4], (B, S, Hkv, D))

        tp = timeit(paged, q, kp, vp, bt, lens)
        tc = timeit(contig, q, kc, vc, lens)
        # interpret-mode kernel steps run in python — keep iters low
        tk = timeit(pallas, q, kp, vp, bt, lens, warmup=1, iters=2)
        t.add(S, bk, round(tp * 1e6, 1), round(tc * 1e6, 1),
              round(tp / tc, 2), round(tk * 1e6, 1), ppb, ns, gb, g1, gx)
    t.show()
    return t
