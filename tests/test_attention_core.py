"""Core attention dispatch: chunked-flash oracle, paged decode paths,
cache read/write round-trips, and hypothesis property tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import cache as kvcache
from repro.core.attention import (decode_attention,
                                  decode_attention_contiguous,
                                  prefill_attention)
from repro.kernels.paged_attention.ref import ring_slot_positions

from conftest import assert_close


@settings(max_examples=15, deadline=None)
@given(B=st.integers(1, 3), S=st.integers(2, 80),
       hkv=st.sampled_from([1, 2, 4]), g=st.sampled_from([1, 2, 4]),
       D=st.sampled_from([8, 32]), window=st.integers(0, 90))
def test_chunked_equals_dense_property(B, S, hkv, g, D, window):
    rng = jax.random.PRNGKey(S * 7 + B)
    ks = jax.random.split(rng, 3)
    H = hkv * g
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, hkv, D))
    v = jax.random.normal(ks[2], (B, S, hkv, D))
    a = prefill_attention(q, k, v, window=window, impl="jnp")
    b = prefill_attention(q, k, v, window=window, impl="chunked")
    assert_close(a, b, rtol=3e-5, atol=3e-5)


def test_prefill_writes_then_gather_roundtrip(rng):
    """write_layer_prefill ∘ gather_layer == identity on live positions."""
    B, S, Hkv, D, ps = 2, 37, 2, 16, 8
    pp = -(-S // ps)
    ks = jax.random.split(rng, 2)
    k = jax.random.normal(ks[0], (B, S, Hkv, D))
    v = jax.random.normal(ks[1], (B, S, Hkv, D))
    lens = jnp.asarray([S, 21], jnp.int32)
    pages = jnp.zeros((B * pp + 2, Hkv, ps, D))
    tables = (jnp.arange(B * pp, dtype=jnp.int32).reshape(B, pp) + 2)
    kp, vp = kvcache.write_layer_prefill(pages, pages, tables, k, v, lens)
    kg, vg = kvcache.gather_layer(kp, vp, tables, S)
    for b in range(B):
        L = int(lens[b])
        assert_close(kg[b, :L], k[b, :L])
        assert_close(vg[b, :L], v[b, :L])
        if L < kg.shape[1]:
            assert np.abs(np.asarray(kg[b, L:])).max() == 0.0


def test_decode_write_then_attend_matches_contiguous(rng):
    B, Hkv, H, D, ps, mp = 2, 2, 4, 16, 8, 4
    ks = jax.random.split(rng, 6)
    kp = jnp.zeros((B * mp, Hkv, ps, D))
    vp = jnp.zeros_like(kp)
    tables = jnp.arange(B * mp, dtype=jnp.int32).reshape(B, mp)
    kc = jnp.zeros((B, mp * ps, Hkv, D))
    vc = jnp.zeros_like(kc)
    lens = np.zeros(B, np.int32)
    for t in range(14):
        kn = jax.random.normal(jax.random.fold_in(ks[0], t), (B, Hkv, D))
        vn = jax.random.normal(jax.random.fold_in(ks[1], t), (B, Hkv, D))
        pos = jnp.full((B,), t, jnp.int32)
        kp, vp = kvcache.write_layer_decode(kp, vp, None, None, pos, kn, vn) \
            if False else kvcache.write_layer_decode(
                kp, vp,
                type("S", (), {"block_tables": tables})(), jnp.arange(B),
                pos, kn, vn)
        kc = kc.at[jnp.arange(B), pos].set(kn)
        vc = vc.at[jnp.arange(B), pos].set(vn)
        lens += 1
    q = jax.random.normal(ks[2], (B, H, D))
    a = decode_attention(q, kp, vp, tables, jnp.asarray(lens), impl="ref")
    b = decode_attention_contiguous(q, kc, vc, jnp.asarray(lens))
    assert_close(a, b, rtol=1e-5, atol=1e-5)


@settings(max_examples=20, deadline=None)
@given(lens=st.lists(st.integers(1, 120), min_size=1, max_size=3),
       ps=st.sampled_from([4, 8]), window=st.integers(4, 40))
def test_ring_slot_positions_property(lens, ps, window):
    """Every live window position is represented exactly once in the ring."""
    ring = -(-window // ps) + 1
    n_slots = ring * ps
    pos = np.asarray(ring_slot_positions(jnp.asarray(lens, jnp.int32), ps,
                                         ring, n_slots))
    for b, L in enumerate(lens):
        live = pos[b][(pos[b] >= 0) & (pos[b] < L) & (pos[b] >= L - window)]
        expect = set(range(max(0, L - window), L))
        assert set(live.tolist()) == expect
        assert len(live) == len(expect)  # no duplicates


def test_decode_attention_window_vs_truncated_contiguous(rng):
    """Sliding-window paged decode == contiguous attention over the window."""
    B, Hkv, H, D, ps, window = 2, 2, 4, 16, 8, 16
    ring = -(-window // ps) + 1
    ks = jax.random.split(rng, 3)
    T = 40
    kc = jax.random.normal(ks[0], (B, T, Hkv, D))
    vc = jax.random.normal(ks[1], (B, T, Hkv, D))
    kp = jnp.zeros((B * ring, Hkv, ps, D))
    vp = jnp.zeros_like(kp)
    tables = jnp.arange(B * ring, dtype=jnp.int32).reshape(B, ring)
    state = type("S", (), {"block_tables": tables})()
    for t in range(T):
        kp, vp = kvcache.write_layer_decode(
            kp, vp, state, jnp.arange(B), jnp.full((B,), t, jnp.int32),
            kc[:, t], vc[:, t], window=window)
    q = jax.random.normal(ks[2], (B, H, D))
    lens = jnp.asarray([T, T - 3], jnp.int32)
    # rewrite len-3 for seq1: its last tokens differ; rebuild for honesty
    a = decode_attention(q, kp, vp, tables, jnp.full((B,), T, jnp.int32),
                         window=window, impl="ref")
    b = decode_attention_contiguous(q, kc, vc, jnp.full((B,), T, jnp.int32),
                                    window=window)
    assert_close(a, b, rtol=1e-5, atol=1e-5)


def test_copy_page_copy_on_write(rng):
    cache = kvcache.init_cache(n_layers=2, num_pages=6, page_size=4,
                               kv_heads=2, head_dim=8, max_seqs=2,
                               max_pages_per_seq=3)
    cache = cache._replace(k_pages=jax.random.normal(rng, cache.k_pages.shape))
    c2 = kvcache.copy_page(cache, jnp.int32(1), jnp.int32(4))
    assert_close(c2.k_pages[:, 4], cache.k_pages[:, 1])
    # NULL src/dst is a no-op
    c3 = kvcache.copy_page(cache, jnp.int32(-1), jnp.int32(2))
    assert_close(c3.k_pages, cache.k_pages)
