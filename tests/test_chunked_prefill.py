"""Chunked paged prefill: kernel conformance + model/engine equivalence.

The acceptance gate for the chunked-prefill contract (ISSUE 5):

  * the prefix-aware paged prefill kernels (TPU scalar-prefetch lowering
    and GPU/Triton lowering) match `ref.paged_prefill_ref` across
    ``pages_per_block`` × ``num_splits`` × ``q_block`` × GQA layouts —
    both share `decode_partition`'s page ranges and the decode kernel's
    ``(m, l, acc)`` partial contract;
  * splitting any prompt into ``prefill_chunk``-token installments
    (resuming each chunk from the cached prefix pages at ``mgr.lens``)
    reproduces the monolithic prefill's logits to <= 1e-5 — at the model
    level for every chunkable family (dense / windowed / VLM / enc-dec)
    and at the engine level for sampled outputs, for chunk sizes of one
    page, two pages, and a non-page-aligned odd size;
  * the chunked scheduler's failure paths are output-transparent: a
    request preempted mid-run re-prefills chunk-by-chunk to the same
    tokens, and a prefill stalled on a dry pool resumes from its cached
    pages (no recompute) with identical output.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.kernels.paged_attention.ops import paged_prefill
from repro.kernels.paged_attention.paged_attention import (
    combine_prefill_partials, paged_prefill_partials)
from repro.kernels.paged_attention.ref import (paged_attention_ref,
                                               paged_prefill_partials_ref,
                                               paged_prefill_ref)
from repro.models.api import build_model
from repro.models.attention import cross_gate
from repro.serving import Engine, Request
from repro.serving.request import Status

from conftest import assert_close

BACKENDS = ["tpu", "gpu"]


# ---------------------------------------------------------------------------
# kernel conformance (both lowerings, one oracle)
# ---------------------------------------------------------------------------
def make_prefill_case(seed, B, H, Hkv, D, page, max_pages, kv_lens, q_start):
    rng = np.random.RandomState(seed)
    num_pages = B * max_pages + 3
    kv_lens = np.asarray(kv_lens, np.int32)
    q_start = np.asarray(q_start, np.int32)
    C = int((kv_lens - q_start).max())
    q = jnp.asarray(rng.randn(B, C, H, D), jnp.float32)
    kp = jnp.asarray(rng.randn(num_pages, Hkv, page, D), jnp.float32)
    vp = jnp.asarray(rng.randn(num_pages, Hkv, page, D), jnp.float32)
    perm = rng.permutation(num_pages)
    tables = np.full((B, max_pages), -1, np.int32)
    k = 0
    for b in range(B):
        n = -(-int(kv_lens[b]) // page)
        tables[b, :n] = perm[k:k + n]
        k += n
    return (q, kp, vp, jnp.asarray(tables), jnp.asarray(kv_lens),
            jnp.asarray(q_start))


PREFILL_SWEEP = [
    # B, H, Hkv, D, page, max_pages, kv_lens, q_start
    (1, 4, 4, 32, 8, 4, [25], [9]),            # MHA, mid-prompt resume
    (2, 8, 2, 16, 8, 5, [29, 11], [13, 0]),    # GQA, mixed resume points
    (2, 4, 1, 16, 4, 6, [23, 8], [0, 3]),      # MQA, whole-prompt row
    (3, 4, 2, 16, 16, 2, [17, 32, 5], [16, 15, 0]),  # single-token chunk row
]


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("case", PREFILL_SWEEP,
                         ids=[str(i) for i in range(len(PREFILL_SWEEP))])
@pytest.mark.parametrize("ppb,splits,q_block", [
    (1, 1, 1), (2, 1, 3), (1, 2, 4), (2, 3, 2), (3, 2, 128),
])
def test_prefill_kernel_matches_ref(case, backend, ppb, splits, q_block):
    q, kp, vp, tables, kv_lens, q_start = make_prefill_case(7, *case)
    ref = paged_prefill_ref(q, kp, vp, tables, kv_lens, q_start)
    out = paged_prefill(q, kp, vp, tables, kv_lens, q_start, impl="pallas",
                        interpret=True, backend=backend,
                        pages_per_block=ppb, num_splits=splits,
                        q_block=q_block)
    # only live chunk rows are specified (padding rows are don't-care)
    for b in range(q.shape[0]):
        ql = int(kv_lens[b] - q_start[b])
        assert_close(out[b, :ql], ref[b, :ql], rtol=1e-5, atol=1e-5)


def test_prefill_partials_match_partials_oracle():
    """The TPU lowering's raw (m, l, acc) partials agree with the split-K
    partials oracle — the shared contract, not just the combined output."""
    case = PREFILL_SWEEP[1]
    q, kp, vp, tables, kv_lens, q_start = make_prefill_case(3, *case)
    D = q.shape[-1]
    kw = dict(scale=1.0 / np.sqrt(D), pages_per_block=2, num_splits=2,
              q_block=3)
    m, l, acc = paged_prefill_partials(q, kp, vp, tables, kv_lens, q_start,
                                       interpret=True, **kw)
    m_r, l_r, acc_r = paged_prefill_partials_ref(q, kp, vp, tables, kv_lens,
                                                 q_start, **kw)
    # live-masked comparison via the combine (dead-partition m encodings
    # may differ in magnitude; what must agree is the merged result) ...
    out = combine_prefill_partials(m, l, acc, q.shape[1], 3)
    out_r = combine_prefill_partials(m_r, l_r, acc_r, q.shape[1], 3)
    for b in range(q.shape[0]):
        ql = int(kv_lens[b] - q_start[b])
        assert_close(out[b, :ql], out_r[b, :ql], rtol=1e-5, atol=1e-5)
    # ... and the per-split mass/max on fully-live rows agree directly
    assert_close(l[0, :, :, :, 0], l_r[0, :, :, :, 0], rtol=1e-5, atol=1e-5)
    assert_close(m[0, :, :, :, 0], m_r[0, :, :, :, 0], rtol=1e-5, atol=1e-5)


def test_prefill_single_token_chunk_equals_decode_oracle():
    """C == 1 with q_start == kv_lens - 1 degenerates to paged decode."""
    q, kp, vp, tables, kv_lens, q_start = make_prefill_case(
        11, 2, 4, 2, 16, 8, 3, [17, 9], [16, 8])
    pre = paged_prefill_ref(q, kp, vp, tables, kv_lens, q_start)
    dec = paged_attention_ref(q[:, 0], kp, vp, tables, kv_lens)
    assert_close(pre[:, 0], dec, rtol=1e-6, atol=1e-6)


def test_prefill_int8_dequant_matches_oracle():
    q, kp, vp, tables, kv_lens, q_start = make_prefill_case(
        5, 2, 4, 2, 16, 8, 4, [30, 12], [8, 0])
    kp8 = jnp.clip(jnp.round(kp / 0.05), -127, 127).astype(jnp.int8)
    vp8 = jnp.clip(jnp.round(vp / 0.05), -127, 127).astype(jnp.int8)
    ref = paged_prefill_ref(q, kp8, vp8, tables, kv_lens, q_start,
                            kv_scale=0.05)
    for backend in BACKENDS:
        out = paged_prefill(q, kp8, vp8, tables, kv_lens, q_start,
                            impl="pallas", interpret=True, backend=backend,
                            kv_scale=0.05, pages_per_block=2, num_splits=2)
        for b in range(q.shape[0]):
            ql = int(kv_lens[b] - q_start[b])
            assert_close(out[b, :ql], ref[b, :ql], rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# model-level: chunked == monolithic logits
# ---------------------------------------------------------------------------
def _mk_state(model, cfg, B, pages_per_seq=8):
    st = {"pos": jnp.zeros((B,), jnp.int32)}
    n_attn = getattr(model, "n_attn_layers", 0)
    Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    num_pages = B * pages_per_seq + 1
    st["k_pages"] = jnp.zeros((n_attn, num_pages, Hkv, cfg.page_size, hd))
    st["v_pages"] = jnp.zeros_like(st["k_pages"])
    st["tables"] = jnp.asarray(
        np.arange(B * pages_per_seq, dtype=np.int32
                  ).reshape(B, pages_per_seq))
    if cfg.family == "encdec":
        ck = (cfg.n_layers, B, cfg.n_audio_frames, Hkv, hd)
        st["cross_k"] = jnp.zeros(ck)
        st["cross_v"] = jnp.zeros(ck)
    elif getattr(model, "n_cross_layers", 0):
        ck = (model.n_cross_layers, B, cfg.n_image_tokens, Hkv, hd)
        st["cross_k"] = jnp.zeros(ck)
        st["cross_v"] = jnp.zeros(ck)
    return st


def _run_chunked(model, params, toks, lens, chunk, extra=None, impl="jnp",
                 state_fn=None):
    """Drive prefill_chunk to completion; returns each row's final-chunk
    logits (the chunked replacement for one monolithic prefill call)."""
    B, _ = toks.shape
    st = state_fn()
    L = np.asarray(lens)
    start = np.zeros((B,), np.int32)
    done = np.zeros((B,), bool)
    logits = None
    tn = np.asarray(toks)
    while not done.all():
        ql = np.maximum(np.minimum(chunk, L - start), 0)
        C = int(ql.max())
        batch = np.zeros((B, C), np.int32)
        for b in range(B):
            batch[b, :ql[b]] = tn[b, start[b]:start[b] + ql[b]]
        lg, st = model.prefill_chunk(
            params, jnp.asarray(batch), st, q_start=jnp.asarray(start),
            q_lens=jnp.asarray(ql), extra=extra, impl=impl)
        if logits is None:
            logits = np.zeros((B, lg.shape[-1]), np.float32)
        newly = (start + ql >= L) & ~done
        logits[newly] = np.asarray(lg)[newly]
        done |= newly
        start = start + ql
    return logits, st


def _page_chunks(ps):
    return [ps, 2 * ps, ps + 3]  # one page, two pages, odd non-aligned


@pytest.mark.parametrize("page_size", [4, 8])
def test_model_chunked_matches_monolithic_dense(page_size):
    cfg = get_smoke("llama2-7b").replace(page_size=page_size)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    B, S = 2, 21
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    lens = jnp.array([S, S - 6], jnp.int32)
    pps = -(-S // page_size) + 1
    mk = lambda: _mk_state(model, cfg, B, pps)
    ref, ref_st = model.prefill(params, toks, mk(), lens=lens, impl="jnp")
    for chunk in _page_chunks(page_size):
        lg, st = _run_chunked(model, params, toks, lens, chunk,
                              state_fn=mk)
        assert_close(lg, ref, rtol=1e-5, atol=1e-5)
        assert_close(st["k_pages"], ref_st["k_pages"], rtol=1e-5, atol=1e-5)


def test_model_chunked_matches_monolithic_pallas_kernel():
    """The chunked path through the Pallas prefill kernel (TPU + GPU
    lowerings) reproduces the monolithic jnp prefill."""
    cfg = get_smoke("llama2-7b")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    B, S = 2, 21
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    lens = jnp.array([S, S - 6], jnp.int32)
    mk = lambda: _mk_state(model, cfg, B)
    ref, _ = model.prefill(params, toks, mk(), lens=lens, impl="jnp")
    lg, _ = _run_chunked(model, params, toks, lens, chunk=8, impl="pallas",
                         state_fn=mk)
    assert_close(lg, ref, rtol=1e-5, atol=1e-5)


def test_model_chunked_matches_monolithic_windowed():
    """'W' layers take the attend-then-write ring fallback — same logits."""
    cfg = get_smoke("llama2-7b").replace(layer_pattern="AW", window=12)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    B, S = 2, 21
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    lens = jnp.array([S, S - 6], jnp.int32)
    mk = lambda: _mk_state(model, cfg, B)
    ref, _ = model.prefill(params, toks, mk(), lens=lens, impl="jnp")
    for chunk in _page_chunks(cfg.page_size):
        lg, _ = _run_chunked(model, params, toks, lens, chunk, state_fn=mk)
        assert_close(lg, ref, rtol=1e-5, atol=1e-5)


def test_model_chunked_matches_monolithic_encdec():
    cfg = get_smoke("whisper-medium")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    B, S = 2, 13
    extra = {"frames": jax.random.normal(
        jax.random.PRNGKey(6), (B, cfg.n_audio_frames, cfg.d_model))}
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    lens = jnp.array([S, S - 4], jnp.int32)
    mk = lambda: _mk_state(model, cfg, B)
    ref, _ = model.prefill(params, toks, mk(), lens=lens, extra=extra,
                           impl="jnp")
    lg, _ = _run_chunked(model, params, toks, lens, 5, extra=extra,
                         state_fn=mk)
    assert_close(lg, ref, rtol=1e-5, atol=1e-5)


def test_model_chunked_encdec_encodes_only_first_chunk_rows():
    """Regression: the encoder gate is per row, not batch-wide.  One
    first-chunk row mixed into three resuming rows must encode a batch
    of exactly that one row's frames (the old gate re-encoded all four
    whenever any row was at chunk 0), and the scattered cross-K/V must
    leave every row's logits identical to the monolithic prefill.  The
    gate is `cross_gate`'s, as the engine computes it from the starts."""
    cfg = get_smoke("whisper-medium")
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    B, S = 4, 8
    extra = {"frames": jax.random.normal(
        jax.random.PRNGKey(6), (B, cfg.n_audio_frames, cfg.d_model))}
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    lens = jnp.array([4, S, S, S], jnp.int32)  # row 0 is one chunk long
    mk = lambda: _mk_state(model, cfg, B)
    ref, _ = model.prefill(params, toks, mk(), lens=lens, extra=extra,
                           impl="jnp")

    enc_batches = []
    orig_encode = model.encode

    def spy(p, frames, impl="jnp"):
        enc_batches.append(int(frames.shape[0]))
        return orig_encode(p, frames, impl)

    model.encode = spy
    tn = np.asarray(toks)
    st = mk()
    # call 1: rows 1-3 take their first chunk; row 0 is not admitted yet
    # and poses as a dead resume (q_start=1, q_lens=0), exactly like the
    # engine's padding rows — it must NOT count as a first-chunk row
    b1 = np.zeros((B, 4), np.int32)
    b1[1:] = tn[1:, :4]
    s1 = np.asarray([1, 0, 0, 0], np.int32)
    _, st = model.prefill_chunk(
        params, jnp.asarray(b1), st, q_start=jnp.asarray(s1),
        q_lens=jnp.asarray([0, 4, 4, 4], jnp.int32), extra=extra,
        **cross_gate(s1))
    # call 2: row 0's first (and only) chunk mixed into three resumes
    b2 = np.zeros((B, 4), np.int32)
    b2[0] = tn[0, :4]
    b2[1:] = tn[1:, 4:]
    s2 = np.asarray([0, 4, 4, 4], np.int32)
    lg, st = model.prefill_chunk(
        params, jnp.asarray(b2), st, q_start=jnp.asarray(s2),
        q_lens=jnp.asarray([4, 4, 4, 4], jnp.int32), extra=extra,
        **cross_gate(s2))
    del model.encode
    assert enc_batches == [3, 1], (
        f"encoder batches {enc_batches}: per-row gate must encode only "
        "the first-chunk rows, not the whole sub-batch")
    assert_close(lg, ref, rtol=1e-5, atol=1e-5)


def test_model_chunked_rejects_recurrent():
    cfg = get_smoke("recurrentgemma-9b")  # pattern RW
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError):
        model.prefill_chunk(params, jnp.zeros((1, 4), jnp.int32), {},
                            jnp.zeros((1,), jnp.int32),
                            jnp.full((1,), 4, jnp.int32))


# ---------------------------------------------------------------------------
# the engine's compiled chunk step == eager model.prefill_chunk
# ---------------------------------------------------------------------------
JIT_CASES = {
    "dense": lambda: get_smoke("llama2-7b"),
    "windowed": lambda: get_smoke("llama2-7b").replace(layer_pattern="AW",
                                                       window=12),
    "moe_dropless": lambda: get_smoke("granite-moe-1b-a400m").replace(
        moe_capacity=0.0),
    "vlm": lambda: get_smoke("llama-3.2-vision-11b"),
    "encdec": lambda: get_smoke("whisper-medium"),
}
# (q_start, q_lens) of successive calls over three rows, chunk 5: every
# row at chunk 0, then all resuming (the last one dead, posing as a resume
# as the engine's padding rows do), then a new first chunk mixed into two
# resumes — for cross-attention models the gate's "full", "reuse" and
# "partial" programs in turn
JIT_CALLS = [([0, 0, 0], [5, 5, 3]), ([5, 5, 3], [5, 2, 0]),
             ([10, 0, 3], [4, 5, 5])]


@pytest.mark.parametrize("case", sorted(JIT_CASES))
def test_jitted_chunk_step_matches_eager(case):
    """The engine's jitted chunk program (``_jit_chunk``) returns the same
    logits and pools as eager ``model.prefill_chunk`` on the same inputs,
    call after call, with the cross-attention gate decided on the host."""
    cfg = JIT_CASES[case]()
    B, C = 3, 5
    eng = Engine(cfg, max_slots=B, max_seq_len=64, prefill_chunk=C,
                 rng=jax.random.PRNGKey(2))
    model, params = eng.model, eng.params
    ctx = None
    if cfg.family == "vlm":
        ctx = {"image_embeds": jax.random.normal(
            jax.random.PRNGKey(4), (B, cfg.n_image_tokens, cfg.d_vision))}
    elif cfg.family == "encdec":
        ctx = {"frames": jax.random.normal(
            jax.random.PRNGKey(4), (B, cfg.n_audio_frames, cfg.d_model))}
    st = _mk_state(model, cfg, B)
    rng = np.random.RandomState(3)
    for i, (starts, lens) in enumerate(JIT_CALLS):
        # a context scaled anew on every call makes the gate observable:
        # a program that recomputed the cross K/V of resuming rows would
        # read the new scale where eager keeps the cached rows
        extra = (None if ctx is None
                 else {k: v * (i + 1) for k, v in ctx.items()})
        starts = np.asarray(starts, np.int32)
        lens = np.asarray(lens, np.int32)
        toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (B, C)), jnp.int32)
        gate = cross_gate(starts) if "cross_k" in st else {}
        lg_j, st_j = eng._jit_chunk(params, toks, st, jnp.asarray(starts),
                                    jnp.asarray(lens), extra, **gate)
        lg_e, st_e = model.prefill_chunk(
            params, toks, st, q_start=jnp.asarray(starts),
            q_lens=jnp.asarray(lens), extra=extra, impl=eng.impl, **gate)
        assert_close(lg_j, lg_e, rtol=1e-5, atol=1e-5)
        for key in ("k_pages", "v_pages", "cross_k", "cross_v"):
            if key in st_e:
                assert_close(st_j[key], st_e[key], rtol=1e-5, atol=1e-5)
        st = dict(st_e)
    # one program per static gate: one for models without cross layers
    modes = {cross_gate(np.asarray(s))["cross_mode"] for s, _ in JIT_CALLS}
    assert eng.stats["chunk_traces"] == (len(modes) if "cross_k" in st
                                         else 1)


def test_engine_chunk_step_compiles_once():
    """Varying occupancy (staggered arrivals, rows finishing) and ragged
    final chunks all run the one compiled chunk program: the engine
    traces it once and dispatches it once per chunk step."""
    cfg = get_smoke("llama2-7b")
    eng = Engine(cfg, max_slots=3, max_seq_len=64, prefill_chunk=8,
                 rng=jax.random.PRNGKey(1))
    arrivals = {0: [[3] * 13, [5] * 2], 2: [[7] * 21], 5: [[9] * 6, [4] * 17]}
    reqs = []
    chunk_steps = 0
    for t in range(300):
        for p in arrivals.get(t, []):
            reqs.append(Request(prompt=list(p), max_new_tokens=4))
            eng.add_request(reqs[-1])
        if t > max(arrivals) and all(r.done for r in reqs):
            break
        before = sum(r.prefill_pos for r in reqs)
        eng.step()
        chunk_steps += sum(r.prefill_pos for r in reqs) > before
    assert all(r.done for r in reqs)
    rep = eng.robustness_report()
    assert chunk_steps >= 5
    assert rep["chunk_steps"] == chunk_steps
    assert rep["chunk_traces"] == 1
    assert eng.mgr.used_pages == 0


# ---------------------------------------------------------------------------
# engine-level: chunked continuous batching == monolithic outputs
# ---------------------------------------------------------------------------
PROMPTS = [[1, 2, 3, 4, 5, 6, 7] * 2, [11, 12, 13], [9] * 25, [4, 5]]


def _reqs(max_new=6):
    return [Request(prompt=list(p), max_new_tokens=max_new) for p in PROMPTS]


@pytest.fixture(scope="module")
def ref_engine():
    cfg = get_smoke("llama2-7b")
    eng = Engine(cfg, max_slots=4, max_seq_len=64, rng=jax.random.PRNGKey(7))
    reqs = _reqs()
    eng.generate(reqs)
    return eng, [list(r.output) for r in reqs]


@pytest.mark.parametrize("chunk", [8, 16, 11])  # page, 2 pages, odd
def test_engine_chunked_matches_monolithic(ref_engine, chunk):
    base, ref_out = ref_engine
    eng = Engine(base.cfg, params=base.params, max_slots=4, max_seq_len=64,
                 rng=jax.random.PRNGKey(7), prefill_chunk=chunk)
    reqs = _reqs()
    eng.generate(reqs, max_steps=500)
    assert [list(r.output) for r in reqs] == ref_out
    assert eng.mgr.used_pages == 0


def test_engine_chunked_bounds_prefill_work(ref_engine):
    """No chunked step prefills more than prefill_chunk tokens per
    request: a long prompt takes ceil(L/chunk) steps to its first token
    while the admitted decodes advance every one of those steps."""
    base, _ = ref_engine
    eng = Engine(base.cfg, params=base.params, max_slots=2, max_seq_len=64,
                 prefill_chunk=8)
    long_req = Request(prompt=[3] * 33, max_new_tokens=2)   # 5 chunks of 8
    short = Request(prompt=[5, 6], max_new_tokens=12)
    eng.add_request(short)
    eng.step()  # short admitted (1 chunk) + first decode
    eng.add_request(long_req)
    decoded_during_prefill = 0
    steps = 0
    while long_req.prefill_pos < len(long_req.prompt) and not long_req.done:
        before = len(short.output)
        eng.step()
        steps += 1
        decoded_during_prefill += len(short.output) - before
        assert long_req.prefill_pos <= steps * 8
        assert steps < 50
    assert steps >= 5  # 33 tokens / 8-token chunks
    assert decoded_during_prefill >= 4  # decode never stalled behind it


def test_engine_chunked_budget_spans_prefill_subbatch(ref_engine):
    """The prefill token budget is global across the prefill sub-batch:
    k concurrent PREFILLING rows split one ``prefill_chunk`` per step —
    they do not each cache a full chunk.  (The former per-request budget
    let a step's prefill work scale as k × chunk, defeating the
    bounded-per-step-work contract; this drives three concurrent
    prefills and asserts the *summed* per-step progress.)"""
    base, _ = ref_engine
    eng = Engine(base.cfg, params=base.params, max_slots=3, max_seq_len=64,
                 prefill_chunk=8, rng=jax.random.PRNGKey(3))
    reqs = [Request(prompt=[3 + i] * 40, max_new_tokens=2) for i in range(3)]
    for r in reqs:
        eng.add_request(r)
    # roomy pool (no preemption): prefill_pos only ever advances, so the
    # per-step delta of the summed positions is exactly the tokens the
    # prefill sub-batch cached that step
    concurrent_prefills = 0
    for _ in range(100):
        if all(r.done for r in reqs):
            break
        n_prefilling = sum(r.status is Status.PREFILLING
                           for r in eng.scheduler.running.values())
        concurrent_prefills = max(concurrent_prefills, n_prefilling)
        before = sum(min(r.prefill_pos, len(r.prompt)) for r in reqs)
        eng.step()
        after = sum(min(r.prefill_pos, len(r.prompt)) for r in reqs)
        assert after - before <= 8, (
            f"prefill sub-batch cached {after - before} tokens in one "
            "step — the chunk budget must span the sub-batch, not apply "
            "per request")
    assert all(r.done for r in reqs)
    assert eng.scheduler.preempted == 0
    assert concurrent_prefills >= 2, (
        "test never had concurrent prefills — the global budget was not "
        "exercised")
    assert eng.mgr.used_pages == 0


def test_engine_chunked_with_preemption_matches(ref_engine):
    """Preemption under an oversubscribed pool stays output-transparent
    with the chunked scheduler (preempted requests re-prefill
    chunk-by-chunk)."""
    base, _ = ref_engine
    # max_new=20 drives peak demand to ~17 pages against a 12-page pool —
    # preemption is guaranteed, not timing-dependent
    ref = _reqs(max_new=20)
    roomy = Engine(base.cfg, params=base.params, max_slots=4, max_seq_len=64,
                   rng=jax.random.PRNGKey(7))
    roomy.generate(ref)
    tight = Engine(base.cfg, params=base.params, max_slots=4, max_seq_len=64,
                   pool_tokens=96, prefill_chunk=8,
                   rng=jax.random.PRNGKey(7))
    reqs = _reqs(max_new=20)
    tight.generate(reqs, max_steps=1000)
    assert tight.scheduler.preempted >= 1, "pool pressure never materialised"
    for a, b in zip(ref, reqs):
        assert a.output == b.output
    assert tight.mgr.used_pages == 0


def test_engine_prefill_stall_resumes_from_cached_pages(ref_engine):
    """A prefill that cannot get its next chunk's pages stalls — keeping
    its cached pages — and resumes from mgr.lens once decode traffic
    frees space.  Output identical to the unconstrained engine, with the
    stall actually exercised and zero preemptions of the stalled
    request."""
    base, _ = ref_engine
    cfg = base.cfg
    long_prompt = [7] * 40
    ref = Request(prompt=list(long_prompt), max_new_tokens=3)
    roomy = Engine(cfg, params=base.params, max_slots=2, max_seq_len=64,
                   rng=jax.random.PRNGKey(9))
    roomy.generate([ref])

    # choreography on a 9-page pool (page_size 8): the short request
    # occupies exactly 5 pages for its whole life (33-token prompt + 7
    # tokens = 40 = page-aligned peak, so extend_for_decode never needs a
    # fresh page → no preemption pressure).  The long 40-token prompt
    # grows one page per 8-token chunk: pages 1..4 fit (9 total used),
    # the 5th chunk finds the pool dry and MUST stall until the short
    # request finishes and frees its pages.
    eng = Engine(cfg, params=base.params, max_slots=2, max_seq_len=64,
                 pool_tokens=72, prefill_chunk=8,
                 rng=jax.random.PRNGKey(9))
    short = Request(prompt=[2] * 33, max_new_tokens=7)
    eng.add_request(short)
    eng.step()
    long_req = Request(prompt=list(long_prompt), max_new_tokens=3)
    eng.add_request(long_req)
    progress = []
    for _ in range(300):
        if long_req.done and short.done:
            break
        eng.step()
        if long_req.status is Status.PREFILLING:
            progress.append(long_req.prefill_pos)
    assert long_req.done and short.done
    assert eng.scheduler.prefill_stalls >= 1, "stall never exercised"
    # resume-from-cached-pages, not restart: the prefill progressed
    # monotonically across the stall (a preempt/restart would reset
    # prefill_pos to 0) and nothing was ever preempted
    assert eng.scheduler.preempted == 0
    # leading zeros are fine: the chunk budget is global across the
    # prefill sub-batch, so the long request may wait while the older
    # short prefill drains its share
    assert progress == sorted(progress) and progress[-1] > 0
    assert max(progress) < 40, "prefill never actually paused mid-prompt"
    assert long_req.output == ref.output
    assert eng.mgr.used_pages == 0


def test_engine_concurrent_prefills_preempt_without_crashing(ref_engine):
    """Regression: several long prompts prefilling concurrently with
    nothing decoding on a tight pool — grow_prefill preempts the youngest
    PREFILLING request mid-loop, in a slot the chunk loop has not visited
    yet.  The loop must skip the vacated slot (it used to KeyError on the
    snapshotted slot list) and every request must still finish with the
    pool returned whole."""
    base, _ = ref_engine
    eng = Engine(base.cfg, params=base.params, max_slots=3, max_seq_len=64,
                 pool_tokens=56, prefill_chunk=8,
                 rng=jax.random.PRNGKey(5))
    reqs = [Request(prompt=[4 + i] * 50, max_new_tokens=2)
            for i in range(3)]  # 3 × 7 pages against an 8-page pool:
    # with the global chunk budget prefills serialise, so the pool must
    # be tight enough that one full prefill (7 pages) plus the two
    # admitted peers' first pages cannot coexist
    eng.generate(reqs, max_steps=600)
    assert all(r.done for r in reqs)
    assert eng.scheduler.preempted >= 1, "pool pressure never materialised"
    assert eng.mgr.used_pages == 0


def test_engine_chunked_rejects_recurrent_families():
    cfg = get_smoke("recurrentgemma-9b")
    with pytest.raises(ValueError, match="recurrent"):
        Engine(cfg, max_slots=2, max_seq_len=64, prefill_chunk=8)


def test_engine_chunked_vlm_with_extras_matches():
    """The modality path: chunked prefill with per-request image extras —
    cross-K/V computed on each request's first chunk, reused (from the
    engine-scattered state rows) on resume chunks."""
    cfg = get_smoke("llama-3.2-vision-11b")
    key = jax.random.PRNGKey(7)
    e1 = Engine(cfg, max_slots=2, max_seq_len=64, rng=key)
    img = np.asarray(jax.random.normal(
        jax.random.PRNGKey(5), (cfg.n_image_tokens, cfg.d_vision)))
    mk = lambda: ([Request(prompt=[3] * 11, max_new_tokens=5),
                   Request(prompt=[8] * 4, max_new_tokens=5)],
                  [{"image_embeds": img}, {"image_embeds": img * 0.5}])
    r1, x1 = mk()
    e1.generate(r1, extras=x1)
    e2 = Engine(cfg, params=e1.params, max_slots=2, max_seq_len=64,
                rng=key, prefill_chunk=4)
    r2, x2 = mk()
    e2.generate(r2, extras=x2, max_steps=300)
    for a, b in zip(r1, r2):
        assert a.output == b.output


def test_engine_chunked_windowed_model_matches():
    """Chunked prefill through a sliding-window model (ring pages take the
    attend-then-write fallback) matches the monolithic engine."""
    cfg = get_smoke("llama2-7b").replace(layer_pattern="AW", window=16)
    e1 = Engine(cfg, max_slots=2, max_seq_len=64, rng=jax.random.PRNGKey(3))
    r1 = [Request(prompt=[7, 11, 13] * 7, max_new_tokens=6)]
    e1.generate(r1)
    e2 = Engine(cfg, params=e1.params, max_slots=2, max_seq_len=64,
                rng=jax.random.PRNGKey(3), prefill_chunk=8)
    r2 = [Request(prompt=[7, 11, 13] * 7, max_new_tokens=6)]
    e2.generate(r2, max_steps=300)
    assert r1[0].output == r2[0].output
