"""Pallas paged-decode kernels vs the pure-jnp oracle (ref.py).

Sweeps shapes / dtypes / GQA ratios / windows / softcap, per the harness
contract: every kernel is validated in interpret mode against ref.py.
The numeric sweeps run per *backend* — the TPU lowering (scalar-prefetch
BlockSpec pipeline) and the GPU/Triton lowering (in-kernel block-table
gathers) are gated against the identical oracles, so neither backend can
drift from the other's semantics.  Off the target hardware both run
through the Pallas interpreter (``interpret=True``); on real TPUs/GPUs
the same tests compile.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import cache as kvcache, paging
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref

from conftest import assert_close

BACKENDS = ["tpu", "gpu"]


def partials_fn(backend):
    """The backend's split-K partials entry point (same contract both)."""
    if backend == "gpu":
        from repro.kernels.paged_attention.paged_attention_gpu import (
            paged_attention_partials_gpu)
        return paged_attention_partials_gpu
    from repro.kernels.paged_attention.paged_attention import (
        paged_attention_partials)
    return paged_attention_partials


def make_case(rng, B, H, Hkv, D, page, max_pages, lens, dtype=jnp.float32,
              scatter=True):
    """Random paged cache with per-seq lens; returns (q, kp, vp, tables, lens)."""
    num_pages = B * max_pages + 3
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (B, H, D), dtype)
    kp = jax.random.normal(ks[1], (num_pages, Hkv, page, D), dtype)
    vp = jax.random.normal(ks[2], (num_pages, Hkv, page, D), dtype)
    # shuffled physical pages (scattered layout — the paper's whole point)
    perm = np.random.RandomState(0).permutation(num_pages)
    tables = np.full((B, max_pages), -1, np.int32)
    lens = np.asarray(lens, np.int32)
    k = 0
    for b in range(B):
        n = -(-int(lens[b]) // page)
        tables[b, :n] = perm[k:k + n]
        k += n
    return q, kp, vp, jnp.asarray(tables), jnp.asarray(lens)


SWEEP = [
    # B, H, Hkv, D, page, max_pages, lens
    (1, 4, 4, 32, 8, 4, [25]),          # MHA
    (3, 8, 2, 64, 16, 4, [64, 17, 1]),  # GQA 4:1
    (2, 16, 1, 128, 8, 3, [24, 9]),     # MQA
    (4, 8, 8, 16, 4, 8, [32, 31, 5, 2]),
    (2, 8, 4, 128, 64, 2, [128, 100]),  # production page size
]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", SWEEP, ids=[str(i) for i in range(len(SWEEP))])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_matches_ref(rng, case, dtype, backend):
    B, H, Hkv, D, page, mp, lens = case
    q, kp, vp, tables, lens = make_case(rng, B, H, Hkv, D, page, mp, lens,
                                        dtype)
    ref = paged_attention_ref(q, kp, vp, tables, lens)
    out = paged_attention(q, kp, vp, tables, lens, impl="pallas",
                          interpret=True, backend=backend)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert_close(out, ref, rtol=tol, atol=tol)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("softcap", [0.0, 30.0])
@pytest.mark.parametrize("window", [0, 12, 40])
def test_kernel_window_softcap(rng, window, softcap, backend):
    B, H, Hkv, D, page = 2, 8, 4, 32, 8
    lens = [61, 23]
    if window > 0:
        ring = -(-window // page) + 1
        mp = ring
        # windowed ring cache: logical page index wraps mod ring
        num_pages = B * mp
        ks = jax.random.split(rng, 3)
        q = jax.random.normal(ks[0], (B, H, D))
        kp = jax.random.normal(ks[1], (num_pages, Hkv, page, D))
        vp = jax.random.normal(ks[2], (num_pages, Hkv, page, D))
        tables = jnp.arange(num_pages, dtype=jnp.int32).reshape(B, mp)
        lens = jnp.asarray(lens, jnp.int32)
    else:
        q, kp, vp, tables, lens = make_case(rng, B, H, Hkv, D, page, 8, lens)
    ref = paged_attention_ref(q, kp, vp, tables, lens, window=window,
                              softcap=softcap)
    out = paged_attention(q, kp, vp, tables, lens, window=window,
                          softcap=softcap, impl="pallas", interpret=True,
                          backend=backend)
    assert_close(out, ref)


@pytest.mark.parametrize("backend", BACKENDS)
def test_kernel_equals_contiguous_attention(rng, backend):
    """The paper's C1: paged == contiguous attention, end to end."""
    B, H, Hkv, D, page, mp = 2, 8, 4, 32, 8, 6
    lens = [41, 29]
    q, kp, vp, tables, lens_a = make_case(rng, B, H, Hkv, D, page, mp, lens)
    # materialise contiguous K/V via Alg.1 GATHER and run dense attention
    k, v = kvcache.gather_layer(kp, vp, tables, mp * page)
    from repro.core.attention import decode_attention_contiguous
    ref = decode_attention_contiguous(q, k, v, lens_a)
    out = paged_attention(q, kp, vp, tables, lens_a, impl="pallas",
                          interpret=True, backend=backend)
    assert_close(out, ref, rtol=1e-4, atol=1e-4)


def test_blockspec_mxu_alignment():
    """Structural check: kernel block shapes are MXU-aligned for the
    production page sizes (DESIGN.md §7)."""
    for page_size in (64, 128):
        assert page_size % 8 == 0  # sublane
    for head_dim in (128,):
        assert head_dim % 128 == 0  # lane


@pytest.mark.parametrize("backend", BACKENDS)
def test_int8_kv_kernel_matches_ref(rng, backend):
    """Beyond-paper int8 KV pages: kernel dequant == ref dequant, and both
    approximate the bf16 result within quantization error."""
    B, H, Hkv, D, page, mp = 2, 8, 4, 32, 8, 4
    q, kp, vp, tables, lens = make_case(rng, B, H, Hkv, D, page, mp, [25, 17])
    scale = 0.035  # ~4.4 sigma for unit-normal KV
    kp8 = jnp.clip(jnp.round(kp / scale), -127, 127).astype(jnp.int8)
    vp8 = jnp.clip(jnp.round(vp / scale), -127, 127).astype(jnp.int8)
    ref8 = paged_attention_ref(q, kp8, vp8, tables, lens, kv_scale=scale)
    out8 = paged_attention(q, kp8, vp8, tables, lens, impl="pallas",
                           interpret=True, kv_scale=scale, backend=backend)
    assert_close(out8, ref8, rtol=1e-4, atol=1e-4)
    exact = paged_attention_ref(q, kp, vp, tables, lens)
    err = float(jnp.max(jnp.abs(ref8 - exact)))
    assert err < 0.2  # quantization-level error, not garbage


def test_fully_masked_row_is_zero(rng):
    """len=0 sequences (dead batch slots) must produce zeros, not NaNs."""
    q, kp, vp, tables, _ = make_case(rng, 2, 4, 4, 16, 8, 2, [9, 16])
    lens = jnp.asarray([9, 0], jnp.int32)
    tables = tables.at[1].set(-1)
    out = paged_attention(q, kp, vp, tables, lens, impl="ref")
    assert not np.isnan(np.asarray(out)).any()
    assert np.abs(np.asarray(out)[1]).max() == 0.0


# ---------------------------------------------------------------------------
# blocked multi-page KV + flash-decoding split-K (kernel v2)

BLOCK_SPLIT_GRID = [(ppb, ns) for ppb in (1, 2, 4) for ns in (1, 3)]
VARIANTS = ["plain", "window", "softcap", "int8"]


def _variant_case(rng, variant):
    """Ragged lens leaving partial blocks AND empty split-K partitions:
    seq1's 2 live pages put every later split's whole range past len."""
    B, H, Hkv, D, page = 2, 8, 4, 32, 8
    if variant == "window":
        window, mp = 20, -(-20 // page) + 1  # ring cache
        num_pages = B * mp
        ks = jax.random.split(rng, 3)
        q = jax.random.normal(ks[0], (B, H, D))
        kp = jax.random.normal(ks[1], (num_pages, Hkv, page, D))
        vp = jax.random.normal(ks[2], (num_pages, Hkv, page, D))
        tables = jnp.arange(num_pages, dtype=jnp.int32).reshape(B, mp)
        lens = jnp.asarray([65, 9], jnp.int32)
        return q, kp, vp, tables, lens, dict(window=window)
    q, kp, vp, tables, lens = make_case(rng, B, H, Hkv, D, page, 9, [65, 9])
    if variant == "softcap":
        return q, kp, vp, tables, lens, dict(softcap=30.0)
    if variant == "int8":
        scale = 0.035
        kp8 = jnp.clip(jnp.round(kp / scale), -127, 127).astype(jnp.int8)
        vp8 = jnp.clip(jnp.round(vp / scale), -127, 127).astype(jnp.int8)
        return q, kp8, vp8, tables, lens, dict(kv_scale=scale)
    return q, kp, vp, tables, lens, {}


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("ppb,ns", BLOCK_SPLIT_GRID)
@pytest.mark.parametrize("variant", VARIANTS)
def test_blocked_splitk_matches_ref(rng, ppb, ns, variant, backend):
    q, kp, vp, tables, lens, kw = _variant_case(rng, variant)
    ref = paged_attention_ref(q, kp, vp, tables, lens, **kw)
    out = paged_attention(q, kp, vp, tables, lens, impl="pallas",
                          interpret=True, pages_per_block=ppb,
                          num_splits=ns, backend=backend, **kw)
    # acceptance bar: split-K path agrees with ref.py to <= 1e-5 max abs
    assert float(jnp.max(jnp.abs(out - ref))) <= 1e-5


@pytest.mark.parametrize("backend", BACKENDS)
def test_splitk_partials_match_ref(rng, backend):
    """Kernel split-K partials == the ref.py partial-softmax oracle, and the
    combine reproduces full attention (incl. empty partitions) — both
    backends emit the identical (m, l, acc) contract."""
    from repro.kernels.paged_attention.paged_attention import combine_partials
    from repro.kernels.paged_attention.ref import (
        combine_partials_ref, paged_attention_partials_ref)

    B, H, Hkv, D, page, mp = 2, 8, 4, 32, 8, 9
    ppb, ns = 2, 3
    q, kp, vp, tables, lens = make_case(rng, B, H, Hkv, D, page, mp, [65, 9])
    scale = 1.0 / np.sqrt(D)
    m, l, acc = partials_fn(backend)(
        q.reshape(B, Hkv, H // Hkv, D), kp, vp, tables, lens, scale=scale,
        interpret=True, pages_per_block=ppb, num_splits=ns)
    mr, lr, accr = paged_attention_partials_ref(
        q, kp, vp, tables, lens, num_splits=ns, pages_per_block=ppb)
    assert float(jnp.max(jnp.abs(m - mr))) <= 1e-5
    assert float(jnp.max(jnp.abs(l - lr))) <= 1e-5
    assert float(jnp.max(jnp.abs(acc - accr))) <= 1e-5
    out = combine_partials(m, l, acc).reshape(B, H, D)
    ref_out = combine_partials_ref(mr, lr, accr)
    assert_close(out, ref_out, rtol=1e-5, atol=1e-5)
    assert_close(out, paged_attention_ref(q, kp, vp, tables, lens),
                 rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", BACKENDS)
def test_empty_split_partition_is_neutral(rng, backend):
    """A split whose whole page range is past len must emit (NEG_INF, 0, 0)
    and change nothing in the combine."""
    from repro.kernels.paged_attention.paged_attention import NEG_INF

    B, H, Hkv, D, page, mp = 1, 4, 2, 16, 4, 8
    q, kp, vp, tables, lens = make_case(rng, B, H, Hkv, D, page, mp, [5])
    m, l, acc = partials_fn(backend)(
        q.reshape(B, Hkv, H // Hkv, D), kp, vp, tables, lens,
        scale=1.0 / np.sqrt(D), interpret=True,
        pages_per_block=1, num_splits=4)
    # pages 2..7 are dead -> splits 1..3 are empty partitions
    assert np.all(np.asarray(m)[:, :, 1:] == NEG_INF)
    assert np.all(np.asarray(l)[:, :, 1:] == 0.0)
    assert np.all(np.asarray(acc)[:, :, 1:] == 0.0)


def test_blocked_kernel_grid_step_reduction():
    """Acceptance: >= 4x fewer grid steps at seq 2048 / page 16 than the
    one-page-per-step baseline, with auto-tuned knobs."""
    from repro.kernels.paged_attention.ops import choose_decode_params
    from repro.kernels.paged_attention.paged_attention import decode_grid_steps

    max_pages = 2048 // 16
    ppb, ns, _ = choose_decode_params(max_pages, 16, 128)
    baseline = decode_grid_steps(max_pages)  # one page per step
    blocked = decode_grid_steps(max_pages, pages_per_block=ppb, num_splits=ns)
    assert baseline == max_pages
    assert blocked * 4 <= baseline


def test_auto_knobs_clamp_to_legal_ranges():
    from repro.kernels.paged_attention.ops import choose_decode_params

    ppb, ns, cm = choose_decode_params(1, 64, 64)  # single-page cache
    assert (ppb, ns) == (1, 1)
    assert cm == "jnp"  # no split-K → no combine kernel
    ppb, ns, cm = choose_decode_params(4, 16, 64, pages_per_block=64,
                                       num_splits=64)
    assert ppb == 4 and ns <= 4  # clamped to the table
    assert cm == ("pallas" if ns > 1 else "jnp")
    ppb, ns, cm = choose_decode_params(256, 16, 128)
    assert ppb * 16 == 128  # MXU-aligned block
    assert 1 <= ns <= 8
    assert cm == "pallas"  # long sequence → split-K → fused combine
    # explicit modes pass through; junk is rejected
    assert choose_decode_params(256, 16, 128, combine_mode="jnp")[2] == "jnp"
    with pytest.raises(ValueError):
        choose_decode_params(256, 16, 128, combine_mode="cuda")


def test_gpu_auto_knobs_warp_shaped():
    """GPU heuristics target warp-width blocks (64 KV tokens, not the
    MXU's 128) and split earlier/wider for SM occupancy."""
    from repro.kernels.paged_attention.ops import choose_decode_params

    ppb_t, ns_t, _ = choose_decode_params(256, 16, 128, backend="tpu")
    ppb_g, ns_g, cm_g = choose_decode_params(256, 16, 128, backend="gpu")
    assert ppb_t * 16 == 128  # MXU-width block
    assert ppb_g * 16 == 64  # warp-width block
    assert ns_g >= ns_t  # GPU splits at least as wide
    assert ns_g <= 16
    # auto combine on GPU is the jnp epilogue even under split-K: the
    # fused combine kernel is a TPU lowering and would run through the
    # interpreter on a real GPU's hot path; explicit "pallas" still works
    assert cm_g == "jnp"
    assert choose_decode_params(256, 16, 128, combine_mode="pallas",
                                backend="gpu")[2] == "pallas"
    # short sequences: single split, no combine kernel — both backends
    assert choose_decode_params(1, 64, 64, backend="gpu") == (1, 1, "jnp")
    # explicit knobs pass through clamping identically on both backends
    assert (choose_decode_params(16, 16, 64, 2, 4, backend="gpu")[:2]
            == choose_decode_params(16, 16, 64, 2, 4, backend="tpu")[:2])


def test_backend_resolution():
    """backend=None auto-resolves from the platform (TPU lowering off-GPU);
    explicit names pass through and junk is rejected."""
    from repro.kernels import resolve_backend

    assert resolve_backend("tpu") == "tpu"
    assert resolve_backend("gpu") == "gpu"
    auto = resolve_backend(None)
    assert auto == ("gpu" if jax.default_backend() == "gpu" else "tpu")
    assert resolve_backend("auto") == auto
    with pytest.raises(ValueError):
        resolve_backend("cuda")


def test_backends_agree_bitwise_partition(rng):
    """Both lowerings share decode_partition, so their outputs agree with
    each other (not just with the oracle) across knob points."""
    q, kp, vp, tables, lens = make_case(rng, 2, 8, 4, 32, 8, 9, [65, 9])
    for ppb, ns in [(1, 1), (2, 3), (4, 2)]:
        o_tpu = paged_attention(q, kp, vp, tables, lens, impl="pallas",
                                interpret=True, pages_per_block=ppb,
                                num_splits=ns, backend="tpu")
        o_gpu = paged_attention(q, kp, vp, tables, lens, impl="pallas",
                                interpret=True, pages_per_block=ppb,
                                num_splits=ns, backend="gpu")
        assert float(jnp.max(jnp.abs(o_tpu - o_gpu))) <= 1e-5


def test_interpreted_kernel_refused_on_tpu(monkeypatch):
    """On a TPU host an interpreted kernel is an error, not a fallback —
    for an explicit interpret=True and for a lowering that would
    auto-resolve to the interpreter (the GPU one)."""
    import repro.kernels as kernels
    from repro.errors import EngineConfigError

    monkeypatch.setattr(kernels, "_on_platform", lambda p: p == "tpu")
    assert kernels.resolve_interpret(None) is False
    with pytest.raises(EngineConfigError, match="interpret mode on a TPU"):
        kernels.resolve_interpret(True)
    with pytest.raises(EngineConfigError, match="interpret mode on a TPU"):
        kernels.resolve_interpret(None, backend="gpu")
