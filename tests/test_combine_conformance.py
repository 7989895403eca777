"""Differential conformance suite for the fused split-K combine (kernel v3).

The gate for shipping the two-kernel decode pipeline: the Pallas combine
kernel must match `ref.combine_partials_ref` within 1e-5 across the full
ppb × splits × {window, softcap, int8 kv_scale, GQA} sweep — including
partitions whose last split is entirely ragged padding blocks — and the
end-to-end pallas-combined decode must match the split-K partials oracle
(`ref.paged_attention_partials_ref` + ref combine).

The end-to-end gates run per *backend*: the TPU decode kernel and the
GPU/Triton decode kernel feed the identical combine (the combine kernel
and both oracles are backend-independent and unchanged), so one
conformance bar covers both lowerings — interpret mode off the target
hardware, compiled on real TPUs/GPUs.

Property-based tests (hypothesis; `tests/_hypothesis_stub.py` when the
real package is absent) pin the combine *algebra*: permutation
invariance over splits, associativity of pairwise merges, all-dead-split
handling (l == 0), and agreement with a single-split run.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
import hypothesis.strategies as st

from repro.kernels.paged_attention.paged_attention import (
    COMBINE_DIM_SEMANTICS, DECODE_DIM_SEMANTICS, NEG_INF,
    _combine_partials_jnp, combine_partials, combine_partials_pallas,
    resolve_combine_mode)
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import (
    combine_partials_ref, paged_attention_partials_ref)

from conftest import assert_close
from test_kernels_paged import BACKENDS, make_case

TOL = 1e-5  # acceptance bar: bit-for-bit within tolerance


# ---------------------------------------------------------------------------
# case builders — every attention variant the kernel supports, with ragged
# lens so the last split covers padding blocks and seq 1 leaves whole
# splits dead
# ---------------------------------------------------------------------------
VARIANTS = ["plain", "gqa", "mqa", "window", "softcap", "int8"]


def _conformance_case(rng, variant):
    page = 8
    if variant == "window":
        window, mp = 20, -(-20 // page) + 1  # bounded ring cache
        B, H, Hkv, D = 2, 8, 4, 32
        num_pages = B * mp
        ks = jax.random.split(rng, 3)
        q = jax.random.normal(ks[0], (B, H, D))
        kp = jax.random.normal(ks[1], (num_pages, Hkv, page, D))
        vp = jax.random.normal(ks[2], (num_pages, Hkv, page, D))
        tables = jnp.arange(num_pages, dtype=jnp.int32).reshape(B, mp)
        lens = jnp.asarray([65, 9], jnp.int32)
        return q, kp, vp, tables, lens, dict(window=window)
    shapes = {  # B, H, Hkv, D — GQA ratios per the acceptance sweep
        "plain": (2, 8, 8, 32),   # MHA
        "gqa": (2, 8, 2, 32),     # 4:1
        "mqa": (2, 8, 1, 64),     # 8:1
        "softcap": (2, 8, 4, 32),
        "int8": (2, 8, 4, 32),
    }
    B, H, Hkv, D = shapes[variant]
    # ragged: seq 0 fills 9 pages minus a partial tail; seq 1 leaves every
    # later split's whole page range dead
    q, kp, vp, tables, lens = make_case(rng, B, H, Hkv, D, page, 9, [65, 9])
    if variant == "softcap":
        return q, kp, vp, tables, lens, dict(softcap=30.0)
    if variant == "int8":
        scale = 0.035
        kp8 = jnp.clip(jnp.round(kp / scale), -127, 127).astype(jnp.int8)
        vp8 = jnp.clip(jnp.round(vp / scale), -127, 127).astype(jnp.int8)
        return q, kp8, vp8, tables, lens, dict(kv_scale=scale)
    return q, kp, vp, tables, lens, {}


def _flat_heads(m):
    """(B, Hkv, S, G) partials → the flat (B, H) head layout ref uses."""
    B, Hkv, _, G = m.shape
    return B, Hkv * G


# ---------------------------------------------------------------------------
# differential sweep: Pallas combine vs ref.combine_partials_ref
# ---------------------------------------------------------------------------
PPB_SPLITS = [(ppb, ns) for ppb in (1, 2, 4) for ns in (2, 3, 4)]


@pytest.mark.parametrize("ppb,ns", PPB_SPLITS)
@pytest.mark.parametrize("variant", VARIANTS)
def test_pallas_combine_matches_ref(rng, ppb, ns, variant):
    """The acceptance gate: kernel combine == oracle combine <= 1e-5 across
    the full ppb × splits × variant sweep (ragged last splits included)."""
    q, kp, vp, tables, lens, kw = _conformance_case(rng, variant)
    m, l, acc = paged_attention_partials_ref(
        q, kp, vp, tables, lens, num_splits=ns, pages_per_block=ppb, **kw)
    B, H = _flat_heads(m)
    out = combine_partials_pallas(m, l, acc).reshape(B, H, -1)
    ref = combine_partials_ref(m, l, acc)
    assert float(jnp.max(jnp.abs(out - ref))) <= TOL


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("ppb,ns", [(2, 3), (4, 2)])
@pytest.mark.parametrize("variant", VARIANTS)
def test_end_to_end_pallas_combine_matches_oracle(rng, ppb, ns, variant,
                                                  backend):
    """Full two-kernel pipeline (decode partials + fused combine) vs the
    split-K oracle pair, end to end — per decode backend, one oracle."""
    q, kp, vp, tables, lens, kw = _conformance_case(rng, variant)
    out = paged_attention(q, kp, vp, tables, lens, impl="pallas",
                          interpret=True, pages_per_block=ppb,
                          num_splits=ns, combine_mode="pallas",
                          backend=backend, **kw)
    m, l, acc = paged_attention_partials_ref(
        q, kp, vp, tables, lens, num_splits=ns, pages_per_block=ppb, **kw)
    ref = combine_partials_ref(m, l, acc)
    assert float(jnp.max(jnp.abs(out - ref))) <= TOL


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("ppb,ns", [(1, 2), (2, 4)])
def test_combine_modes_agree_end_to_end(rng, ppb, ns, backend):
    """jnp-epilogue and fused-kernel decodes are interchangeable."""
    q, kp, vp, tables, lens, _ = _conformance_case(rng, "gqa")
    o_jnp = paged_attention(q, kp, vp, tables, lens, impl="pallas",
                            interpret=True, pages_per_block=ppb,
                            num_splits=ns, combine_mode="jnp",
                            backend=backend)
    o_pal = paged_attention(q, kp, vp, tables, lens, impl="pallas",
                            interpret=True, pages_per_block=ppb,
                            num_splits=ns, combine_mode="pallas",
                            backend=backend)
    assert float(jnp.max(jnp.abs(o_jnp - o_pal))) <= TOL


def test_megacore_dimension_semantics():
    """(batch, kv_head, split) are parallel; only the scratch-accumulating
    block axis is sequential.  The combine grid is fully parallel."""
    assert DECODE_DIM_SEMANTICS == ("parallel", "parallel", "parallel",
                                    "arbitrary")
    assert COMBINE_DIM_SEMANTICS == ("parallel", "parallel")


def test_resolve_combine_mode():
    assert resolve_combine_mode(None, 1) == "jnp"
    assert resolve_combine_mode(None, 4) == "pallas"
    assert resolve_combine_mode("auto", 8) == "pallas"
    assert resolve_combine_mode("jnp", 8) == "jnp"
    assert resolve_combine_mode("pallas", 1) == "pallas"
    with pytest.raises(ValueError):
        resolve_combine_mode("triton", 2)


# ---------------------------------------------------------------------------
# property-based algebra tests (hypothesis / deterministic stub)
# ---------------------------------------------------------------------------
def _random_partials(seed, B, Hkv, S, G, D, dead_splits=()):
    """Plausible split-K partials: m ~ N(0,1)·sqrt(D), l > 0, acc free;
    listed splits are dead ((NEG_INF, 0, 0) — the kernel's empty-partition
    contract)."""
    r = np.random.RandomState(seed)
    m = r.randn(B, Hkv, S, G).astype(np.float32) * np.sqrt(D)
    l = np.abs(r.randn(B, Hkv, S, G)).astype(np.float32) + 0.1
    acc = r.randn(B, Hkv, S, G, D).astype(np.float32)
    for s in dead_splits:
        m[:, :, s] = NEG_INF
        l[:, :, s] = 0.0
        acc[:, :, s] = 0.0
    return jnp.asarray(m), jnp.asarray(l), jnp.asarray(acc)


def _merge2(a, b):
    """Pairwise stable merge of two partials — the associativity witness."""
    m1, l1, a1 = a
    m2, l2, a2 = b
    m = jnp.maximum(m1, m2)
    c1 = jnp.exp(m1 - m)
    c2 = jnp.exp(m2 - m)
    return m, l1 * c1 + l2 * c2, a1 * c1[..., None] + a2 * c2[..., None]


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), S=st.integers(2, 6),
       G=st.integers(1, 4), rnd=st.randoms())
def test_combine_permutation_invariant(seed, S, G, rnd):
    """Split order is an implementation detail of the grid walk — any
    permutation of the split axis must combine to the same output."""
    m, l, acc = _random_partials(seed, 2, 2, S, G, 8, dead_splits=(S - 1,))
    perm = list(range(S))
    rnd.shuffle(perm)
    p = jnp.asarray(perm)
    base = combine_partials_pallas(m, l, acc)
    shuf = combine_partials_pallas(m[:, :, p], l[:, :, p], acc[:, :, p])
    assert_close(base, shuf, rtol=TOL, atol=TOL)


@settings(max_examples=10, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), S=st.integers(3, 6))
def test_pairwise_merge_associative(seed, S):
    """Left-fold, right-fold and one-shot combines agree: the merge is
    associative, so megacore may reduce splits in any tree shape."""
    m, l, acc = _random_partials(seed, 1, 2, S, 2, 8)
    parts = [(m[:, :, s], l[:, :, s], acc[:, :, s]) for s in range(S)]
    left = parts[0]
    for p in parts[1:]:
        left = _merge2(left, p)
    right = parts[-1]
    for p in reversed(parts[:-1]):
        right = _merge2(p, right)
    o_left = left[2] / jnp.maximum(left[1], 1e-30)[..., None]
    o_right = right[2] / jnp.maximum(right[1], 1e-30)[..., None]
    assert_close(o_left, o_right, rtol=TOL, atol=TOL)
    one_shot = combine_partials_pallas(m, l, acc)
    assert_close(one_shot, o_left, rtol=TOL, atol=TOL)


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), S=st.integers(1, 5))
def test_all_dead_splits_yield_zero(seed, S):
    """A (b, h, g) slot whose every split is dead (l == 0) is a masked row:
    exact zeros, never NaN — in both combine implementations."""
    m, l, acc = _random_partials(seed, 2, 2, S, 2, 8,
                                 dead_splits=tuple(range(S)))
    for out in (combine_partials_pallas(m, l, acc),
                _combine_partials_jnp(m, l, acc)):
        a = np.asarray(out)
        assert not np.isnan(a).any()
        assert np.abs(a).max() == 0.0


@settings(max_examples=6, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), ns=st.integers(2, 5),
       ppb=st.sampled_from([1, 2, 4]))
def test_split_run_agrees_with_single_split(seed, ns, ppb):
    """Combining ns-way partials of a real attention case reproduces the
    single-split (no split-K) result."""
    rng = jax.random.PRNGKey(seed)
    q, kp, vp, tables, lens = make_case(rng, 2, 4, 2, 16, 8, 6, [41, 3])
    m1, l1, a1 = paged_attention_partials_ref(
        q, kp, vp, tables, lens, num_splits=1, pages_per_block=ppb)
    mn, ln, an = paged_attention_partials_ref(
        q, kp, vp, tables, lens, num_splits=ns, pages_per_block=ppb)
    single = combine_partials_pallas(m1, l1, a1)
    multi = combine_partials_pallas(mn, ln, an)
    assert_close(single, multi, rtol=TOL, atol=TOL)


def test_combine_dispatcher_auto():
    """combine_partials(None) routes by split count and both routes agree."""
    m, l, acc = _random_partials(0, 2, 2, 4, 2, 8)
    auto = combine_partials(m, l, acc)  # S=4 → pallas
    assert_close(auto, _combine_partials_jnp(m, l, acc), rtol=TOL, atol=TOL)
    m1, l1, a1 = _random_partials(1, 2, 2, 1, 2, 8)
    assert_close(combine_partials(m1, l1, a1),
                 combine_partials_pallas(m1, l1, a1), rtol=TOL, atol=TOL)
