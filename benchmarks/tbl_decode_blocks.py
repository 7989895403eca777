"""Decode-kernel tuning sweep: backend × pages_per_block × num_splits ×
combine_mode.

For each knob combination this reports the grid-step count per
(batch, kv_head) pair, interpret-mode wall time, and max abs error vs the
jnp oracle — so a perf win is never a silent correctness loss.  Each
(ppb, splits) point runs under both split-K combine implementations
("jnp" epilogue vs the fused "pallas" kernel); ``jnp_vs_pallas`` is the
max abs divergence between the two, the bench-level echo of the
conformance suite's 1e-5 gate.

The ``backend`` axis runs the same sweep through both kernel lowerings —
the TPU scalar-prefetch pipeline and the GPU/Triton in-kernel gather —
each with its own auto-tuned row (`choose_decode_params` targets
MXU-width blocks on TPU, warp-width on GPU).  ``--backend tpu|gpu``
restricts the axis; default sweeps both.

``grid_steps`` is the hardware-relevant metric: on a real TPU each grid
step pays fixed pipeline overhead and a sliver-shaped matmul, so fewer,
fatter steps (ppb·page_size = 128 KV tokens) feed the MXU at full width,
and split-K adds parallel grid slots for long single sequences.  On GPU
the same count is CTAs' inner-loop trips; split-K there buys SM
occupancy.  ``us_per_call`` is CPU interpret mode, where python-level
per-*page* work dominates instead — it validates semantics and tracks
relative knob cost, not hardware speed.

The ``auto`` rows are `choose_decode_params`, the heuristic the serving
engine uses when the knobs are left unset.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from benchmarks.common import Table, timeit
from repro.core.attention import choose_decode_params, decode_attention
from repro.kernels.paged_attention.paged_attention import decode_grid_steps

PAGE_SIZE = 16
SEQ_LEN = 1024
B = 2
HKV, G, D = 2, 4, 64  # GQA 4:1
BACKENDS = ("tpu", "gpu")


def _case(seq_len: int):
    mp = -(-seq_len // PAGE_SIZE)
    H = HKV * G
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (B, H, D))
    kp = jax.random.normal(ks[1], (B * mp, HKV, PAGE_SIZE, D))
    vp = jax.random.normal(ks[2], (B * mp, HKV, PAGE_SIZE, D))
    bt = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
    lens = jnp.asarray([seq_len, seq_len - 3 * PAGE_SIZE - 5], jnp.int32)
    return q, kp, vp, bt, lens, mp


def run(fast: bool = False, backend: Optional[str] = None):
    seq_len = 256 if fast else SEQ_LEN
    q, kp, vp, bt, lens, mp = _case(seq_len)
    ref = decode_attention(q, kp, vp, bt, lens, impl="ref")

    sweep = ([(1, 1), (8, 1), (8, 4)] if fast else
             [(1, 1), (2, 1), (4, 1), (8, 1), (8, 2), (8, 4), (8, 8),
              (4, 4), (16, 4)])
    backends = (backend,) if backend else BACKENDS

    t = Table(f"tbl_decode_blocks_s{seq_len}",
              ["backend", "ppb_x_splits", "combine", "us_per_call",
               "grid_steps", "max_abs_err", "jnp_vs_pallas"])
    for be in backends:
        # label rows with the *effective* (clamped) knobs, deduped — a
        # short sequence clamps num_splits down and a mislabeled row would
        # read as "split-K costs more for nothing"
        ppb_a, ns_a, cm_auto = choose_decode_params(mp, PAGE_SIZE, D,
                                                    backend=be)
        rows = [("auto", ppb_a, ns_a)]
        seen = {(ppb_a, ns_a)}
        for req in sweep:
            ppb_e, ns_e, _ = choose_decode_params(mp, PAGE_SIZE, D, *req,
                                                  backend=be)
            if (ppb_e, ns_e) not in seen:
                seen.add((ppb_e, ns_e))
                rows.append(("fixed", ppb_e, ns_e))

        for tag, ppb, ns in rows:
            steps = decode_grid_steps(mp, pages_per_block=ppb, num_splits=ns)
            label = f"{ppb}x{ns}" + ("_auto" if tag == "auto" else "")
            outs, uss, errs = {}, {}, {}
            for cm in ("jnp", "pallas"):
                fn = jax.jit(
                    lambda q, kp, vp, bt, l, ppb=ppb, ns=ns, cm=cm, be=be:
                    decode_attention(q, kp, vp, bt, l, impl="pallas",
                                     pages_per_block=ppb,
                                     num_splits=ns, combine_mode=cm,
                                     backend=be))
                uss[cm] = timeit(fn, q, kp, vp, bt, lens,
                                 warmup=1, iters=2) * 1e6
                outs[cm] = fn(q, kp, vp, bt, lens)
                errs[cm] = float(jnp.max(jnp.abs(outs[cm] - ref)))
            div = float(jnp.max(jnp.abs(outs["jnp"] - outs["pallas"])))
            for cm in ("jnp", "pallas"):
                # '*' marks the mode the auto-tuner picks for these knobs
                star = "*" if (tag == "auto" and cm == cm_auto) else ""
                t.add(be, label, cm + star, round(uss[cm], 1), steps,
                      f"{errs[cm]:.2e}", f"{div:.2e}")
    t.show()
    return t
