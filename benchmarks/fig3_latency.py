"""Paper Fig. 3: inference latency vs sequence length, with/without the
global KV cache.

The paper's claim (C2): with the paged KV cache, per-token latency grows
~linearly as context grows 128→2048; without caching (re-running the full
prefix every token) it grows ~like the square (reported "exponential" —
~10× per doubling on their stack).  We reproduce the *scaling shapes* on
CPU with the reduced model; absolute numbers are CPU-scale.

Second axis (prefix-cache PR): the same latency-vs-context question one
level up — TTFT for a *repeated* prompt, cold vs warm through the global
prefix cache.  A warm hit skips the cached pages' prefill entirely, so
warm TTFT stays ~flat in the shared-prefix length while cold TTFT grows
with it.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Table, Tables, timeit
from repro.configs import get_smoke
from repro.configs.base import RunConfig
from repro.models.api import build_model

SEQ_LENS = [128, 256, 512, 1024, 2048]


def _prefix_cache_axis(fast: bool) -> Table:
    """Engine-level TTFT for an identical prompt, cold vs warm."""
    from repro.serving import Engine, Request

    cfg = get_smoke("llama2-7b")
    probe = Engine(cfg, max_slots=1, max_seq_len=8)  # params donor
    lens = [64, 128] if fast else [64, 128, 256]
    t = Table("fig3_prefix_cache",
              ["prompt_len", "cold_ms", "warm_ms", "ttft_ratio",
               "hit_tokens", "pages_saved"])
    for L in lens:
        eng = Engine(cfg, params=probe.params, max_slots=2,
                     max_seq_len=L + 16, prefix_cache=True)

        def ttft(tok, L=L, eng=eng):
            r = Request(prompt=[tok] * L, max_new_tokens=2)
            eng.add_request(r)
            t0 = time.perf_counter()
            while not r.output and not r.done:
                eng.step()
            dt = (time.perf_counter() - t0) * 1e3
            while not r.done:
                eng.step()
            return dt, r

        # compile both code paths off the clock (the warm resume runs a
        # different prefill shape than the cold monolithic pass)
        ttft(3)
        ttft(3)
        cold_ms, _ = ttft(5)   # distinct tokens: guaranteed cache miss
        warm_ms, r = ttft(5)   # identical prompt: attach + suffix only
        assert r.cached_prefix > 0, "warm run never hit the cache"
        t.add(L, round(cold_ms, 2), round(warm_ms, 2),
              round(cold_ms / max(warm_ms, 1e-9), 2), r.cached_prefix,
              r.cached_prefix // cfg.page_size)
    t.show()
    return t


def run(fast: bool = False, backend: str = None, prefix_cache: str = None):
    cfg = get_smoke("llama2-7b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    seq_lens = SEQ_LENS[:3] if fast else SEQ_LENS
    t = Table("fig3_latency",
              ["seq_len", "backend", "cached_us_tok", "uncached_us_tok",
               "ratio"])
    bk = backend or "auto"

    # --backend picks the cached path's decode-kernel lowering (the
    # oracle impl ignores it; impl="pallas" exercises it end-to-end)
    impl = "ref" if backend is None else "pallas"
    decode = jax.jit(lambda p, tok, st: model.decode_step(
        p, tok, st, impl=impl, backend=backend))
    forward = jax.jit(lambda p, toks: model.forward(p, toks))

    rows = []
    for S in seq_lens:
        B = 1
        run_cfg = RunConfig(model=cfg, seq_len=S + 8, global_batch=B,
                            kind="decode")
        st = model.init_decode_state(run_cfg)
        b, n_sh, pps = st["tables"].shape
        st["tables"] = jnp.arange(b * n_sh * pps,
                                  dtype=jnp.int32).reshape(b, n_sh, pps)
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                  cfg.vocab_size)
        _, st = model.prefill(params, toks, st)
        tok = jnp.ones((B,), jnp.int32)

        # cached: one decode step against an S-token cache
        t_cached = timeit(decode, params, tok, st)
        # uncached: regenerate the whole prefix every new token
        t_uncached = timeit(forward, params, toks)
        rows.append((S, t_cached, t_uncached))
        t.add(S, bk, round(t_cached * 1e6, 1), round(t_uncached * 1e6, 1),
              round(t_uncached / t_cached, 1))

    # C2 scaling check: cached grows sub-linearly vs uncached growth
    c0, cN = rows[0][1], rows[-1][1]
    u0, uN = rows[0][2], rows[-1][2]
    span = rows[-1][0] / rows[0][0]
    t.add("growth_x", bk, round(cN / c0, 2), round(uN / u0, 2),
          f"context x{span:.0f}")
    t.show()
    if prefix_cache == "off":
        return t
    return Tables(t, _prefix_cache_axis(fast))
