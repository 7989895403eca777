"""Serving-engine integration tests: continuous batching over paged KV.

Covers the paper's system claims: paged == contiguous outputs (C1),
oversubscription + preemption correctness, <5% memory overhead (objective
§I-B), scheduler fairness, and mixed-length batches (§IV scenario b).
"""

import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.serving import Engine, Request, Status
from repro.serving.scheduler import Scheduler
from repro.core.paging import HostPageManager


def make_engine(arch="llama2-7b", **kw):
    cfg = get_smoke(arch)
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_seq_len", 64)
    return Engine(cfg, **kw)


def test_paged_equals_contiguous_generation():
    cfg = get_smoke("llama2-7b")
    e1 = Engine(cfg, max_slots=2, max_seq_len=64, rng=jax.random.PRNGKey(7))
    e2 = Engine(cfg, params=e1.params, paged=False, max_slots=2,
                max_seq_len=64, rng=jax.random.PRNGKey(7))
    prompts = [[1, 2, 3, 4, 5, 6, 7], [11, 12, 13]]
    r1 = [Request(prompt=list(p), max_new_tokens=10) for p in prompts]
    r2 = [Request(prompt=list(p), max_new_tokens=10) for p in prompts]
    e1.generate(r1)
    e2.generate(r2)
    for a, b in zip(r1, r2):
        assert a.output == b.output


def test_oversubscribed_pool_preempts_and_recovers():
    eng = make_engine(pool_tokens=128)  # 4 slots x 64 would need 256
    reqs = [Request(prompt=[1] * 40, max_new_tokens=8) for _ in range(4)]
    eng.generate(reqs, max_steps=400)
    assert all(r.done for r in reqs)
    assert all(len(r.output) == 8 for r in reqs)
    assert eng.scheduler.preempted >= 1  # pressure actually happened
    assert eng.mgr.used_pages == 0  # everything reclaimed


def test_preempted_request_output_is_unchanged():
    """Preemption must be output-transparent (recompute path)."""
    cfg = get_smoke("llama2-7b")
    key = jax.random.PRNGKey(3)
    roomy = Engine(cfg, max_slots=4, max_seq_len=64, rng=key)
    tight = Engine(cfg, params=roomy.params, max_slots=4, max_seq_len=64,
                   pool_tokens=96, rng=key)
    mk = lambda: [Request(prompt=[7] * (20 + 5 * i), max_new_tokens=6)
                  for i in range(4)]
    a, b = mk(), mk()
    roomy.generate(a)
    tight.generate(b, max_steps=500)
    assert tight.scheduler.preempted >= 1
    for ra, rb in zip(a, b):
        assert ra.output == rb.output


def test_memory_overhead_objective():
    """<5% overhead vs theoretical minimum while serving (paper §I-B)."""
    eng = make_engine(max_slots=4, max_seq_len=256)
    reqs = [Request(prompt=[1] * n, max_new_tokens=4)
            for n in (100, 150, 200, 220)]
    for r in reqs:
        eng.add_request(r)
    eng.step()  # admit + prefill
    rep = eng.memory_report()
    assert rep["overhead_frac"] < 0.05
    # the contiguous baseline for the same batch wastes >50%
    base = Engine(eng.cfg, params=eng.params, paged=False, max_slots=4,
                  max_seq_len=256)
    for r in [Request(prompt=[1] * n, max_new_tokens=4)
              for n in (100, 150, 200, 220)]:
        base.add_request(r)
    base.step()
    assert base.memory_report()["overhead_frac"] > 0.5


def test_memory_report_uses_pool_dtype():
    """int8 pools must be accounted at their own itemsize: sizing them by
    the f32 activation dtype overstated pool_bytes/reserved_bytes 4× and
    skewed the paper's <5% overhead metric."""
    import dataclasses

    import jax.numpy as jnp

    cfg = get_smoke("llama2-7b")
    cfg8 = dataclasses.replace(cfg, kv_dtype="int8")
    eng = Engine(cfg, max_slots=2, max_seq_len=64)
    eng8 = Engine(cfg8, max_slots=2, max_seq_len=64)
    assert eng8.state["k_pages"].dtype == jnp.int8
    for e in (eng, eng8):
        e.add_request(Request(prompt=[1] * 20, max_new_tokens=4))
        e.step()
    rep, rep8 = eng.memory_report(), eng8.memory_report()
    ratio = jnp.dtype(eng.dtype).itemsize  # f32 pools vs 1-byte int8 pools
    assert rep8["pool_bytes"] * ratio == rep["pool_bytes"]
    assert rep8["reserved_bytes"] * ratio == rep["reserved_bytes"]
    assert rep8["theoretical_min_bytes"] * ratio == rep["theoretical_min_bytes"]
    # the ratio metric is itemsize-invariant once accounting is consistent
    assert abs(rep8["overhead_frac"] - rep["overhead_frac"]) < 1e-9


def test_ttft_and_throughput_metrics():
    eng = make_engine()
    reqs = [Request(prompt=[1, 2, 3], max_new_tokens=5)]
    eng.generate(reqs)
    m = reqs[0].metrics
    assert m["ttft_s"] > 0 and m["tok_s"] > 0


def test_eos_stops_generation():
    eng = make_engine()
    # eos_id impossible (vocab) vs guaranteed: use a token the model will
    # emit by forcing max_new_tokens large and eos from the first sample
    r = Request(prompt=[1, 2, 3], max_new_tokens=40)
    eng.generate([r])
    eos = r.output[0]
    r2 = Request(prompt=[1, 2, 3], max_new_tokens=40, eos_id=eos)
    eng2 = Engine(eng.cfg, params=eng.params, max_slots=4, max_seq_len=64)
    eng2.generate([r2])
    assert len(r2.output) == 1 and r2.output[0] == eos


def test_many_waves_through_few_slots():
    """More requests than slots: continuous batching drains the queue."""
    eng = make_engine(max_slots=2)
    reqs = [Request(prompt=[i + 1] * (5 + i), max_new_tokens=4)
            for i in range(7)]
    eng.generate(reqs, max_steps=500)
    assert all(r.done for r in reqs)
    assert eng.mgr.used_pages == 0


def test_engine_fuzz_random_waves():
    """Property: any mix of request lengths/budgets completes under an
    oversubscribed pool, and every page is reclaimed afterwards."""
    import numpy as np
    cfg = get_smoke("llama2-7b")
    eng = Engine(cfg, max_slots=3, max_seq_len=96, pool_tokens=192)
    rng = np.random.default_rng(42)
    reqs = []
    for wave in range(3):
        wave_reqs = [
            Request(prompt=[int(x) for x in
                            rng.integers(1, 200, size=rng.integers(1, 80))],
                    max_new_tokens=int(rng.integers(1, 10)),
                    temperature=float(rng.choice([0.0, 1.0])))
            for _ in range(4)
        ]
        reqs += wave_reqs
        eng.generate(wave_reqs, max_steps=800)
    assert all(r.done for r in reqs)
    assert all(len(r.output) == r.max_new_tokens for r in reqs)
    assert eng.mgr.used_pages == 0
    assert not eng.scheduler.running and not eng.scheduler.waiting
    # refcounts all zero, free list complete
    assert sorted(eng.mgr.free_list) == list(range(eng.num_pages))
    assert all(c == 0 for c in eng.mgr.refcount)


def test_fork_prefix_sharing_is_exact_and_copy_on_write():
    """Paper §III contribution 1: fork aliases full pages (no recompute,
    no copy) and the forked branch produces exactly what a fresh request
    with the same prefix would."""
    cfg = get_smoke("llama2-7b")
    key = jax.random.PRNGKey(11)
    eng = Engine(cfg, max_slots=3, max_seq_len=96, rng=key)
    parent = Request(prompt=[5] * 20, max_new_tokens=24)
    eng.add_request(parent)
    # run until the parent has generated half its budget
    while len(parent.output) < 12:
        eng.step()
    pages_before = eng.mgr.used_pages
    child = eng.fork_request(parent, max_new_tokens=6)
    # alias accounting: at most one fresh (tail) page was allocated
    assert eng.mgr.used_pages - pages_before <= 1
    seq_at_fork = list(child.prompt)
    while not child.done:
        eng.step()
    # reference: a fresh engine continuing the same prefix greedily
    ref_eng = Engine(cfg, params=eng.params, max_slots=1, max_seq_len=96)
    ref = Request(prompt=seq_at_fork, max_new_tokens=6)
    ref_eng.generate([ref])
    assert child.output == ref.output
    # parent unaffected and still correct
    while not parent.done:
        eng.step()
    ref2 = Request(prompt=[5] * 20, max_new_tokens=24)
    ref_eng2 = Engine(cfg, params=eng.params, max_slots=1, max_seq_len=96)
    ref_eng2.generate([ref2])
    assert parent.output == ref2.output


def test_tables_array_refuses_silent_truncation():
    """ISSUE 5 satellite: a sequence whose page row outgrows the device
    table width must be a hard error.  The former code silently did
    ``row[:pages_per_seq]`` — the sequence attended over a dropped KV
    tail and produced wrong output with no signal."""
    eng = make_engine(max_slots=2, max_seq_len=32)  # pages_per_seq = 4
    req = Request(prompt=[1] * 10, max_new_tokens=4)
    eng.add_request(req)
    eng.step()
    # force the host row past the device table width (the overflow a
    # mis-sized fork or an unchecked extend would produce)
    assert eng.mgr.reserve(req.rid, eng.max_seq_len + 1)
    with pytest.raises(RuntimeError, match="refusing to truncate"):
        eng._tables_array()


def test_tables_array_ring_models_still_truncate_by_design():
    """Windowed models are the sanctioned exception: their row is a ring
    and row[:ring] IS the device table (slots overwritten in place)."""
    cfg = get_smoke("llama2-7b").replace(layer_pattern="W", window=16)
    eng = Engine(cfg, max_slots=2, max_seq_len=64)
    assert eng.pages_per_seq == 3  # ceil(16/8) + 1
    req = Request(prompt=[1] * 30, max_new_tokens=4)
    eng.add_request(req)
    eng.step()  # host row is 4 pages > ring 3 — must NOT raise
    t = eng._tables_array()
    assert (t[req.slot, 0] >= 0).all()


def test_fork_exceeding_max_seq_len_raises():
    """The overflow path that used to reach the silent truncation: a fork
    whose child would outgrow max_seq_len mid-decode."""
    eng = make_engine(max_slots=3, max_seq_len=32)
    parent = Request(prompt=[1] * 20, max_new_tokens=4)
    eng.add_request(parent)
    eng.step()
    with pytest.raises(ValueError, match="max_seq_len"):
        eng.fork_request(parent, max_new_tokens=32)


# ---------------------------------------------------------------------------
# scheduler unit tests
# ---------------------------------------------------------------------------
def test_scheduler_fifo_admission():
    mgr = HostPageManager(num_pages=8, page_size=8)
    sch = Scheduler(mgr, max_slots=2, max_seq_len=64)
    r1 = Request(prompt=[0] * 30)   # 4 pages + 1 headroom
    r2 = Request(prompt=[0] * 30)
    r3 = Request(prompt=[0] * 8)
    for r in (r1, r2, r3):
        sch.add(r)
    admitted = sch.admit()
    # r1 fits (5), r2 doesn't (only 3 pages left) and BLOCKS r3 (FIFO)
    assert [r.rid for _, r in admitted] == [r1.rid]
    assert r2.status == Status.WAITING and r3.status == Status.WAITING


def test_scheduler_preempts_youngest():
    mgr = HostPageManager(num_pages=4, page_size=8)
    sch = Scheduler(mgr, max_slots=2, max_seq_len=64, headroom_pages=0)
    r1 = Request(prompt=[0] * 16)  # 2 pages
    r2 = Request(prompt=[0] * 16)  # 2 pages
    sch.add(r1)
    sch.add(r2)
    assert len(sch.admit()) == 2
    # both full; extending forces preemption of the youngest (r2)
    victims = sch.extend_for_decode()
    assert [v.rid for v in victims] == [r2.rid]
    assert r2.status == Status.PREEMPTED
    assert r1.status == Status.RUNNING
    assert sch.waiting[0] is r2  # re-queued at the front


def test_pallas_decode_engine_matches_ref_engine():
    """The serving decode path with the blocked/split-K Pallas kernel
    (explicit knobs) generates the same tokens as the jnp-oracle engine."""
    from repro.configs import get_smoke

    cfg = get_smoke("llama2-7b")
    outs = []
    for kw in (dict(impl="ref"),
               dict(impl="pallas", pages_per_block=2, num_splits=2)):
        eng = Engine(cfg, max_slots=2, max_seq_len=64,
                     rng=jax.random.PRNGKey(3), **kw)
        req = Request(prompt=[7, 11, 13] * 4, max_new_tokens=8,
                      temperature=0.0)
        eng.generate([req])
        outs.append(list(req.output))
    assert outs[0] == outs[1]


def test_generate_raises_when_max_steps_leaves_requests_unfinished():
    from repro.serving import DeadlineExceeded
    eng = make_engine()
    reqs = [Request(prompt=[1, 2, 3], max_new_tokens=8)]
    with pytest.raises(DeadlineExceeded, match="1 of 1 requests unfinished"):
        eng.generate(reqs, max_steps=3)
    assert not reqs[0].done


@pytest.mark.parametrize("chunk,pool", [(None, 128), (16, 64)],
                         ids=["monolithic", "chunked"])
def test_ttft_counters_split_queue_wait_from_prefill(chunk, pool):
    """Under preemption each request counts one admission and one first
    token; ``queue_wait_ns`` and ``prefill_ns`` are the sums of arrival ->
    first admission and first admission -> first token."""
    eng = make_engine(pool_tokens=pool, prefill_chunk=chunk)
    reqs = [Request(prompt=[1] * 40, max_new_tokens=8) for _ in range(6)]
    eng.generate(reqs, max_steps=400)
    rep = eng.robustness_report()
    assert rep["preempted"] >= 1
    assert rep["admitted"] == rep["first_tokens"] == len(reqs)
    m = [r.metrics for r in reqs]
    assert rep["queue_wait_ns"] == pytest.approx(
        sum(x["t_admit"] - x["t_arrive"] for x in m) * 1e9, abs=len(m))
    assert rep["prefill_ns"] == pytest.approx(
        sum(x["t_arrive"] + x["ttft_s"] - x["t_admit"] for x in m) * 1e9,
        abs=len(m) * 10)
