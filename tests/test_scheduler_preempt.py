"""Preemption/fork stress test: allocator invariants under an
oversubscribed pool.

PagedAttention's serving half is only correct if the scheduler that frees
pages "instantly" under memory pressure and the allocator that hands them
out agree at every step.  Two historical bugs broke that agreement:

  * `Scheduler.extend_for_decode` iterated a *snapshot* list while
    preempting — the rebinding ``order = [...]`` never affected the
    active ``for`` loop — so ``mgr.extend`` ran on victims whose pages
    were just freed, re-reserving pages under PREEMPTED rids; the stale
    table row survived ``tables.setdefault`` on re-admission and aliased
    pages concurrently allocated to other sequences.
  * `HostPageManager.fork` ignored the ``bool`` from ``reserve`` — on a
    dry pool the child kept the shared-prefix refcount bumps but got no
    tail page (and pre-fix returned ``None``, so callers could not even
    tell).

This suite fails on the pre-fix scheduler/manager and gates the fixed
ones: every step of an interleaved admit/extend/preempt/fork/finish
schedule must preserve the allocator invariants below.
"""

import random

import pytest

from repro.core.paging import HostPageManager
from repro.serving.request import Request, Status
from repro.serving.scheduler import Scheduler


def check_allocator_invariants(mgr: HostPageManager, sched: Scheduler):
    """The host-allocator ↔ scheduler agreement, asserted exhaustively."""
    live_rids = {r.rid for r in sched.running.values()}

    # 1. pages are only ever held under RUNNING rids — a table row under a
    #    preempted/finished rid is a ghost reservation (the extend-after-
    #    preempt bug's signature) that admission control cannot see.
    assert set(mgr.tables) == live_rids, (
        f"table rows exist for non-running rids: "
        f"{set(mgr.tables) - live_rids}")
    assert set(mgr.lens) == live_rids

    # 2. refcounts match table occurrences exactly.
    occ = {}
    for row in mgr.tables.values():
        for p in row:
            occ[p] = occ.get(p, 0) + 1
    for p in range(mgr.num_pages):
        assert mgr.refcount[p] == occ.get(p, 0), (
            f"page {p}: refcount {mgr.refcount[p]} != "
            f"{occ.get(p, 0)} table occurrences")

    # 3. no physical page referenced by two live block tables unless its
    #    refcount says so (prefix sharing) — refcount 1 means sole owner.
    for p, n in occ.items():
        if n >= 2:
            assert mgr.refcount[p] >= 2, f"page {p} aliased at refcount 1"

    # 4. free-list conservation: every page is free xor referenced, no
    #    duplicates, and the used/free split covers the whole pool.
    free = set(mgr.free_list)
    assert len(free) == len(mgr.free_list), "duplicate pages on free list"
    assert not (free & set(occ)), "page simultaneously free and referenced"
    assert mgr.used_pages + len(mgr.free_list) == mgr.num_pages
    assert len(occ) + len(mgr.free_list) == mgr.num_pages

    # 5. table rows cover exactly ceil(len / page_size) pages.
    for rid, row in mgr.tables.items():
        want = -(-mgr.lens[rid] // mgr.page_size)
        assert len(row) == want, (
            f"rid {rid}: {len(row)} pages for len {mgr.lens[rid]}")


def _drain_running_decode_token(sched: Scheduler):
    """Mirror the engine: every surviving RUNNING request gains the token
    the extend reserved space for.  (PREFILLING requests are still caching
    their prompt — they neither extend nor sample.)"""
    for r in sched.running.values():
        if r.status is Status.RUNNING:
            r.output.append(0)


def test_preempted_victim_is_never_extended():
    """Targeted regression for the extend-after-preempt bug: the victim
    preempted mid-loop sits *later* in the rid-sorted iteration order, so
    the buggy loop reached it after its pages were freed and re-reserved
    a page under the PREEMPTED rid."""
    mgr = HostPageManager(num_pages=6, page_size=4)
    sched = Scheduler(mgr, max_slots=2, max_seq_len=64, headroom_pages=1)
    r0 = Request(prompt=[1] * 8, max_new_tokens=32)
    r1 = Request(prompt=[1] * 8, max_new_tokens=32)
    sched.add(r0)
    sched.add(r1)
    assert len(sched.admit()) == 2

    victims = []
    for _ in range(8):
        victims += sched.extend_for_decode()
        _drain_running_decode_token(sched)
        check_allocator_invariants(mgr, sched)
        if victims:
            break
    assert victims == [r1], "youngest running request must be the victim"
    assert r1.status is Status.PREEMPTED
    # the freed rid must hold nothing: no table row, no len, no pages —
    # pre-fix, mgr.tables[r1.rid] re-appeared with one freshly-popped page
    assert r1.rid not in mgr.tables
    assert r1.rid not in mgr.lens
    # and the survivor keeps decoding with a consistent allocator
    assert r0.rid in mgr.tables
    check_allocator_invariants(mgr, sched)


def test_fork_on_dry_pool_rolls_back():
    """`HostPageManager.fork` must be all-or-nothing: a fork whose tail
    page cannot be served returns False and leaves no trace (pre-fix it
    returned None, kept the refcount bumps, and left a tail-less child
    row behind)."""
    mgr = HostPageManager(num_pages=3, page_size=4)
    assert mgr.reserve(0, 9)  # 3 pages: 2 full + partial tail; pool now dry
    before_ref = list(mgr.refcount)
    ok = mgr.fork(0, 1)
    assert ok is False
    assert 1 not in mgr.tables and 1 not in mgr.lens
    assert mgr.refcount == before_ref, "failed fork must roll back refcounts"
    assert len(mgr.free_list) == 0

    # page-aligned src (no tail needed) forks fine even on a dry pool
    mgr2 = HostPageManager(num_pages=2, page_size=4)
    assert mgr2.reserve(0, 8)
    assert mgr2.fork(0, 1) is True
    assert mgr2.tables[1] == mgr2.tables[0]
    assert all(mgr2.refcount[p] == 2 for p in mgr2.tables[0])


def test_fork_from_unknown_src_raises_invariant_error():
    """`fork` from a rid with no table row (never reserved, already
    freed, or preempted) is a scheduler invariant violation and must
    raise a structured error naming the rid — pre-fix it escaped as a
    bare ``KeyError`` from the table lookup, indistinguishable from an
    allocator bug."""
    from repro.errors import SchedulerInvariantError

    mgr = HostPageManager(num_pages=4, page_size=4)
    with pytest.raises(SchedulerInvariantError, match="unknown rid 99"):
        mgr.fork(99, 1)

    # fork-after-free is the same violation (the preempt/fork race)
    assert mgr.reserve(0, 8)
    mgr.free(0)
    with pytest.raises(SchedulerInvariantError, match="unknown rid 0"):
        mgr.fork(0, 1)
    # nothing leaked by the refused forks
    assert len(mgr.free_list) == mgr.num_pages
    assert not mgr.tables and not mgr.lens


def test_double_free_after_fork():
    """Freeing a fork child twice must fail loudly on the second free and
    leave the parent's shared pages (and the pool accounting) intact."""
    from repro.errors import SchedulerInvariantError

    mgr = HostPageManager(num_pages=4, page_size=4)
    assert mgr.reserve(0, 8)
    assert mgr.fork(0, 1) is True
    parent_pages = list(mgr.tables[0])
    mgr.free(1)
    assert all(mgr.refcount[p] == 1 for p in parent_pages)
    with pytest.raises(SchedulerInvariantError):
        mgr.free(1)
    # the double free must not have touched the parent's pages
    assert mgr.tables[0] == parent_pages
    assert all(mgr.refcount[p] == 1 for p in parent_pages)
    assert mgr.used_pages == 2
    mgr.free(0)
    assert len(mgr.free_list) == mgr.num_pages


def test_preempt_fork_stress_invariants():
    """The acceptance stress: oversubscribed pool, N steps of interleaved
    admits / decode-extends (with preemption) / forks / finishes, with the
    full allocator-invariant check after every step."""
    rnd = random.Random(0xC0FFEE)
    mgr = HostPageManager(num_pages=24, page_size=4)
    sched = Scheduler(mgr, max_slots=4, max_seq_len=256, headroom_pages=1)

    all_reqs = []

    def submit(n_tokens):
        r = Request(prompt=[1] * n_tokens, max_new_tokens=rnd.randint(4, 24))
        all_reqs.append(r)
        sched.add(r)

    for _ in range(3):
        submit(rnd.randint(4, 24))

    preempted_total = 0
    forked_total = 0
    fork_failed_total = 0
    for step in range(200):
        # keep pressure on: top the queue up so admission always has work
        if len(sched.waiting) < 2 and rnd.random() < 0.5:
            submit(rnd.randint(4, 28))

        sched.admit()
        check_allocator_invariants(mgr, sched)

        if sched.running:
            preempted_total += len(sched.extend_for_decode())
            _drain_running_decode_token(sched)
            check_allocator_invariants(mgr, sched)

        # fork: child aliases a running parent's full pages (refcount++).
        # On a dry pool the fork must fail atomically — either way the
        # invariants hold.  The child enters the running batch directly
        # (no re-prefill), mirroring Engine.fork_request.
        free_slots = sched.free_slots()
        if sched.running and free_slots and rnd.random() < 0.35:
            parent = rnd.choice(list(sched.running.values()))
            child = Request(prompt=list(parent.prompt) + list(parent.output),
                            max_new_tokens=rnd.randint(2, 8))
            all_reqs.append(child)
            ok = mgr.fork(parent.rid, child.rid)
            assert ok in (True, False), "fork must report success"
            if ok:
                child.status = Status.RUNNING
                child.slot = free_slots[0]
                sched.running[child.slot] = child
                forked_total += 1
            else:
                fork_failed_total += 1
                assert child.rid not in mgr.tables
            check_allocator_invariants(mgr, sched)

        # finish requests that hit their budget (frees pages → churn)
        for r in list(sched.running.values()):
            if len(r.output) >= r.max_new_tokens:
                sched.finish(r)
        check_allocator_invariants(mgr, sched)

    # the schedule must actually have exercised the hard paths
    assert preempted_total >= 3, "stress never triggered preemption"
    assert forked_total >= 3, "stress never forked"
    assert sched.preempted == preempted_total

    # drain: let everything finish; the pool must come back whole
    for _ in range(600):
        if not sched.has_work:
            break
        sched.admit()
        if sched.running:
            sched.extend_for_decode()
            _drain_running_decode_token(sched)
        for r in list(sched.running.values()):
            if len(r.output) >= r.max_new_tokens:
                sched.finish(r)
        check_allocator_invariants(mgr, sched)
    assert not sched.has_work
    assert len(mgr.free_list) == mgr.num_pages
    assert all(c == 0 for c in mgr.refcount)


def test_chunked_admission_reserves_chunkwise_not_total():
    """ISSUE 5 satellite: admission must reserve prompt pages chunk-wise.
    The former all-at-front reservation head-of-line-blocked the whole
    queue on a long prompt's full page count even though chunked prefill
    grows incrementally."""
    # 8 pages of 8 tokens.  A 50-token prompt needs 7 pages + headroom
    # monolithically — more than the pool ever has once anything else
    # runs; chunk-wise it needs 1 page + headroom.
    mono_mgr = HostPageManager(num_pages=8, page_size=8)
    mono = Scheduler(mono_mgr, max_slots=3, max_seq_len=128)
    chunk_mgr = HostPageManager(num_pages=8, page_size=8)
    chunked = Scheduler(chunk_mgr, max_slots=3, max_seq_len=128,
                        prefill_chunk=8)
    for sched in (mono, chunked):
        sched.add(Request(prompt=[1] * 24))  # 3 pages, admitted by both
        sched.add(Request(prompt=[1] * 50))  # long
        sched.add(Request(prompt=[1] * 8))   # short, behind the long one

    a_mono = mono.admit()
    # monolithic: long blocks (needs 7+1 of the 5 remaining) and FIFO
    # blocks the short one behind it
    assert len(a_mono) == 1
    assert mono.waiting[0].prompt_len == 50
    assert mono.waiting[1].status is Status.WAITING

    a_chunk = chunked.admit()
    # chunk-wise: the long prompt is admitted on one chunk's pages, so
    # the short request behind it is admitted sooner (same step)
    assert len(a_chunk) == 3
    assert all(r.status is Status.PREFILLING for _, r in a_chunk)
    check_allocator_invariants(chunk_mgr, chunked)


def _drive_prefill_chunks(sched: Scheduler):
    """Mirror Engine._prefill_chunk_step against the scheduler alone:
    grow each PREFILLING request by one chunk (stall on a dry pool) and
    flip it to RUNNING when its last chunk lands."""
    progressed = []
    for r in sorted(sched.running.values(), key=lambda x: x.rid):
        if r.status is not Status.PREFILLING:
            continue
        if sched.running.get(r.slot) is not r:
            continue  # preempted by an earlier grow_prefill this step
        if not sched.grow_prefill(r):
            continue  # stalled: keeps pages, resumes later
        if sched.running.get(r.slot) is not r:
            continue  # grow_prefill preempted it to make progress
        r.prefill_pos = min(r.prefill_pos + sched.prefill_chunk,
                            r.total_len)
        if r.prefill_pos >= r.total_len:
            r.status = Status.RUNNING
            progressed.append(r)
    return progressed


def test_chunked_preempt_midprefill_readmit_finish_stress():
    """ISSUE 5 satellite: the preemption stress with the chunked-prefill
    state machine in the loop — admit (chunk-wise) → grow/stall chunks →
    decode-extend (preempting PREFILLING victims too) → re-admit → finish
    — asserting the same allocator invariants every step."""
    rnd = random.Random(0xBEEF)
    mgr = HostPageManager(num_pages=20, page_size=4)
    sched = Scheduler(mgr, max_slots=4, max_seq_len=256, headroom_pages=1,
                      prefill_chunk=8)

    all_reqs = []

    def submit(n_tokens):
        r = Request(prompt=[1] * n_tokens,
                    max_new_tokens=rnd.randint(4, 16))
        all_reqs.append(r)
        sched.add(r)

    for _ in range(3):
        submit(rnd.randint(12, 40))

    preempted_midprefill = 0
    finished = 0
    for step in range(300):
        if len(sched.waiting) < 2 and rnd.random() < 0.6:
            submit(rnd.randint(12, 48))

        sched.admit()
        check_allocator_invariants(mgr, sched)

        pre_prefilling = {r.rid: r.prefill_pos
                          for r in sched.running.values()
                          if r.status is Status.PREFILLING}
        _drive_prefill_chunks(sched)
        check_allocator_invariants(mgr, sched)

        if any(r.status is Status.RUNNING for r in sched.running.values()):
            victims = sched.extend_for_decode()
            preempted_midprefill += sum(
                1 for v in victims if v.rid in pre_prefilling)
            _drain_running_decode_token(sched)
            check_allocator_invariants(mgr, sched)

        for r in list(sched.running.values()):
            if r.status is Status.RUNNING and \
                    len(r.output) >= r.max_new_tokens:
                sched.finish(r)
                finished += 1
        check_allocator_invariants(mgr, sched)

    # the schedule must have exercised the chunked hard paths
    assert sched.preempted >= 3, "stress never preempted"
    assert preempted_midprefill >= 1, \
        "no request was ever preempted mid-prefill"
    assert sched.prefill_stalls >= 1, "no prefill ever stalled"
    assert finished >= 5

    # a mid-prefill preemptee must re-admit from chunk 0 and finish
    for _ in range(800):
        if not sched.has_work:
            break
        sched.admit()
        _drive_prefill_chunks(sched)
        if any(r.status is Status.RUNNING for r in sched.running.values()):
            sched.extend_for_decode()
            _drain_running_decode_token(sched)
        for r in list(sched.running.values()):
            if r.status is Status.RUNNING and \
                    len(r.output) >= r.max_new_tokens:
                sched.finish(r)
        check_allocator_invariants(mgr, sched)
    assert not sched.has_work
    assert all(r.status is Status.FINISHED for r in all_reqs)
    assert len(mgr.free_list) == mgr.num_pages
    assert all(c == 0 for c in mgr.refcount)


def test_grow_prefill_stalls_then_resumes_without_losing_pages():
    """A prefill stalled on a dry pool keeps its reservation (mgr.lens
    unchanged) and continues from it — never from zero — once pages free."""
    mgr = HostPageManager(num_pages=6, page_size=4)
    sched = Scheduler(mgr, max_slots=2, max_seq_len=128, headroom_pages=1,
                      prefill_chunk=8)
    decoder = Request(prompt=[1] * 12, max_new_tokens=4)  # 3 pages
    long_req = Request(prompt=[1] * 40, max_new_tokens=4)
    sched.add(decoder)
    sched.add(long_req)
    assert len(sched.admit()) == 2
    # decoder's prompt caches in two chunks (8 then 4): 3 pages total
    assert sched.grow_prefill(decoder)
    decoder.prefill_pos = 8
    assert sched.grow_prefill(decoder)
    decoder.prefill_pos = 12
    decoder.status = Status.RUNNING

    # admission already reserved the first chunk (8 tokens = 2 pages)
    assert sched.grow_prefill(long_req)
    long_req.prefill_pos = 8
    # the next chunk (to 16 tokens = 4 pages) needs 2 pages, free is 1:
    # stall — a RUNNING decoder will free pages, so no preemption
    assert not sched.grow_prefill(long_req), "pool should be dry"
    assert sched.prefill_stalls == 1
    assert mgr.lens[long_req.rid] == 8, "stall must not touch the reservation"
    assert long_req.status is Status.PREFILLING
    assert sched.preempted == 0

    sched.finish(decoder)  # frees 3 pages
    assert sched.grow_prefill(long_req)
    assert mgr.lens[long_req.rid] == 16  # resumed from 8, not from 0
    check_allocator_invariants(mgr, sched)


def test_cascaded_preemption_keeps_invariants():
    """Several sequences hitting page boundaries in the same step force
    multiple victims in one extend pass; each later extend must see the
    post-preemption allocator, never a stale snapshot."""
    mgr = HostPageManager(num_pages=9, page_size=4)
    sched = Scheduler(mgr, max_slots=3, max_seq_len=128, headroom_pages=1)
    reqs = [Request(prompt=[1] * 8, max_new_tokens=64) for _ in range(3)]
    for r in reqs:
        sched.add(r)
    assert len(sched.admit()) == 3  # 6 pages used, 3 free

    victims = []
    for _ in range(10):
        victims += sched.extend_for_decode()
        _drain_running_decode_token(sched)
        check_allocator_invariants(mgr, sched)
        if len(victims) >= 2:
            break
    assert len(victims) >= 2, "pool pressure must force multiple victims"
    for v in victims:
        assert v.status is Status.PREEMPTED
        assert v.rid not in mgr.tables and v.rid not in mgr.lens
    # exactly one survivor decodes on
    assert len(sched.running) == 1
    check_allocator_invariants(mgr, sched)


def test_admission_counters_count_first_admissions(monkeypatch):
    """``queue_wait_ns`` sums each request's wait from ``t_arrive`` to its
    first admission, however many steps it waited; a preempted request's
    re-admission adds to neither ``admitted`` nor ``queue_wait_ns``."""
    import repro.serving.scheduler as scheduler_mod
    clock = [0.0]
    monkeypatch.setattr(scheduler_mod.time, "perf_counter",
                        lambda: clock[0])
    mgr = HostPageManager(num_pages=32, page_size=4)
    sched = Scheduler(mgr, max_slots=2, max_seq_len=64, headroom_pages=1)
    r0, r1, r2 = (Request(prompt=[1] * 8, max_new_tokens=8)
                  for _ in range(3))
    for r in (r0, r1, r2):
        r.metrics["t_arrive"] = 0.0
        sched.add(r)
    clock[0] = 1.0
    assert [r for _, r in sched.admit()] == [r0, r1]
    assert (sched.admitted, sched.queue_wait_ns) == (2, 2_000_000_000)
    for _ in range(3):  # r2 waits three steps for a slot
        clock[0] += 1.0
        assert sched.admit() == []
    sched.finish(r0)
    clock[0] = 5.0
    assert [r for _, r in sched.admit()] == [r2]
    assert (sched.admitted, sched.queue_wait_ns) == (3, 7_000_000_000)
    sched._preempt(r1)
    clock[0] = 6.0
    assert [r for _, r in sched.admit()] == [r1]
    assert sched.preempted == 1
    assert (sched.admitted, sched.queue_wait_ns) == (3, 7_000_000_000)
    assert r1.metrics["t_admit"] == 1.0
    check_allocator_invariants(mgr, sched)
