"""statemachine: bounded exhaustive model checking of the request
lifecycle.

``test_scheduler_preempt.py`` / ``test_prefix_cache.py`` *sample* the
scheduler+allocator state space with stress soaks; this rule *enumerates*
it.  The transition relation (admit → attach-prefix, chunk-grow, extend
(+preempt/resume), fork, cancel, fail, finish, evict) is factored behind
``LifecycleDriver`` — a pure driver over ``Scheduler`` /
``HostPageManager`` / ``PrefixCache`` whose every action runs on a
``clone()`` of the state — and BFS explores **every** interleaving of
enabled actions for small bounded configurations (``CONFIGS``: ≤3
requests × ≤8 pages × ≤2 pages per request, plus a prefix-cache-enabled
configuration), asserting at every reachable state:

  * ``refcount[p] == table occurrences of p + cache residency`` (the
    generalized allocator invariant — catches leaked refcount bumps such
    as the historical fork-without-rollback bug);
  * table rows belong only to LIVE requests / tracked forked rows (a row
    under a PREEMPTED or terminal rid is the historical
    extend-after-preempt aliasing bug);
  * free-list conservation: no duplicates, no referenced page on the
    list, ``free + referenced == num_pages``;
  * row geometry: ``len(row) == ceil(lens / page_size)``;
  * terminal cleanliness: terminal requests hold no slot/row, and when
    everything is terminal only cache-resident pages stay off the free
    list.

BFS order makes the first counterexample **minimal**: the finding
message carries the shortest action trace reaching the violation (read
left to right; each step is one driver action with its request id).

Fixture support: a file assigning ``REPLINT_STATEMACHINE_CASES`` (a
module-level list of ``(label, driver_factory)``) is loaded by path and
each factory's state space is explored — re-seeding a historical bug
into a ``LifecycleDriver`` subclass demonstrably rediscovers it (gated
by ``tests/test_statemachine.py``).  On the live tree the rule runs the
real driver over ``CONFIGS`` when it reaches ``serving/scheduler.py``.
"""

from __future__ import annotations

import ast
import importlib.util
from collections import Counter, deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, FrozenSet, List, Optional, Sequence, Tuple

from repro.analysis.core import FileContext, Finding, Project, register

RULE = "statemachine"
FIXTURE_CASES = "REPLINT_STATEMACHINE_CASES"
MAX_STATES = 200_000
FORK_RID_BASE = 100


# ---------------------------------------------------------------------------
# bounded configurations
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class ModelConfig:
    """One bounded exploration: every field is part of the proof's scope."""

    name: str
    num_pages: int
    page_size: int
    max_slots: int
    prompts: Tuple[Tuple[int, ...], ...]
    prefill_chunk: Optional[int] = None  # None = monolithic prefill
    max_new: int = 1                     # decode tokens per request
    fork: bool = False                   # enable the copy-on-write action
    cache: bool = False                  # wire a PrefixCache in
    headroom: int = 0
    # injected-teardown budgets: each run may cancel/fail at most this
    # many requests (the teardown paths are fully covered with 1; an
    # unbounded budget multiplies the space without new behaviors)
    cancel_budget: int = 1
    fail_budget: int = 1


# ≤3 requests × ≤8 pages × ≤2 pages per request, per the bounded-model
# contract documented in README §Static analysis.
CONFIGS: Tuple[ModelConfig, ...] = (
    # chunked prefill under pool pressure: stall/preempt/resume paths
    ModelConfig(name="chunked-preempt", num_pages=4, page_size=2,
                max_slots=2, prompts=((1, 2, 3), (1, 2, 3), (4, 5)),
                prefill_chunk=2),
    # monolithic + fork: copy-on-write tail reservation on a tight pool
    ModelConfig(name="fork-cow", num_pages=3, page_size=2, max_slots=2,
                prompts=((1, 2, 3), (4, 5)), fork=True),
    # prefix cache: attach/retain/evict interleaved with the lifecycle
    # (r0 and r2 share their full prefix; r1 diverges after one page)
    ModelConfig(name="prefix-cache", num_pages=6, page_size=2, max_slots=2,
                prompts=((1, 2, 3), (1, 2, 4), (1, 2, 3)), cache=True),
)


# ---------------------------------------------------------------------------
# the pure driver
# ---------------------------------------------------------------------------
class LifecycleDriver:
    """The scheduler/page-manager transition relation behind a pure
    interface: ``enabled()`` lists applicable actions, ``apply()``
    executes one, ``clone()`` branches the whole state, ``violations()``
    evaluates the allocator invariants.  Buggy fixture drivers override
    individual ``_do_*`` methods to re-seed historical defects."""

    def __init__(self, cfg: ModelConfig):
        # imports live here so the analysis package stays importable
        # without jax (paging pulls it in)
        from repro.core.paging import HostPageManager
        from repro.serving.request import Request
        from repro.serving.scheduler import Scheduler

        self.cfg = cfg
        mgr = HostPageManager(cfg.num_pages, cfg.page_size)
        cache = None
        if cfg.cache:
            from repro.core.prefix_cache import PrefixCache
            cache = PrefixCache(mgr)
        self.sched = Scheduler(
            mgr, max_slots=cfg.max_slots,
            max_seq_len=max(len(p) for p in cfg.prompts) + cfg.max_new,
            headroom_pages=cfg.headroom, prefill_chunk=cfg.prefill_chunk,
            prefix_cache=cache)
        self.requests = []
        for i, prompt in enumerate(cfg.prompts):
            req = Request(prompt=list(prompt), max_new_tokens=cfg.max_new,
                          rid=i)
            self.requests.append(req)
            self.sched.add(req)
        self.forked: FrozenSet[int] = frozenset()
        self.fork_count = 0
        self.cancel_count = 0
        self.fail_count = 0

    # -- cloning ---------------------------------------------------------
    def clone(self) -> "LifecycleDriver":
        from repro.serving.request import Request
        from repro.serving.scheduler import Scheduler

        new = object.__new__(type(self))
        new.cfg = self.cfg
        mgr = self.sched.mgr.clone()
        cache = self.sched.cache.clone(mgr) if self.sched.cache else None

        def clone_req(r):
            c = Request(prompt=list(r.prompt),
                        max_new_tokens=r.max_new_tokens, rid=r.rid)
            c.status = r.status
            c.slot = r.slot
            c.prefill_pos = r.prefill_pos
            c.cached_prefix = r.cached_prefix
            c.output = list(r.output)
            c.parent = r.parent
            c.error = r.error
            return c

        by_rid = {r.rid: clone_req(r) for r in self.requests}
        s = self.sched
        sched = object.__new__(Scheduler)
        sched.mgr = mgr
        sched.cache = cache
        for attr in ("max_slots", "max_seq_len", "headroom",
                     "prefill_chunk", "max_waiting", "admit_watermark",
                     "preempted", "prefill_stalls", "shed", "failed",
                     "cancelled", "deadline_misses", "admitted",
                     "queue_wait_ns"):
            setattr(sched, attr, getattr(s, attr))
        sched.waiting = [by_rid[r.rid] for r in s.waiting]
        sched.running = {slot: by_rid[r.rid]
                         for slot, r in s.running.items()}
        sched.failed_events = [by_rid[r.rid] for r in s.failed_events
                               if r.rid in by_rid]
        new.sched = sched
        new.requests = [by_rid[r.rid] for r in self.requests]
        new.forked = self.forked
        new.fork_count = self.fork_count
        new.cancel_count = self.cancel_count
        new.fail_count = self.fail_count
        return new

    # -- the transition relation ----------------------------------------
    def enabled(self) -> List[Tuple]:
        from repro.serving.request import Status, TERMINAL

        sched = self.sched
        actions: List[Tuple] = []
        if sched.waiting and len(sched.running) < sched.max_slots:
            actions.append(("admit",))
        live = list(sched.running.values())
        for r in live:
            if r.status is Status.PREFILLING:
                actions.append(("grow", r.rid))
        if any(r.status is Status.RUNNING for r in live):
            actions.append(("decode",))
        for r in live:
            if r.status is Status.RUNNING:
                actions.append(("finish", r.rid))
                if self.cfg.fork and self.fork_count < 1:
                    actions.append(("fork", r.rid))
            if self.fail_count < self.cfg.fail_budget:
                actions.append(("fail", r.rid))
        if self.cancel_count < self.cfg.cancel_budget:
            for r in self.requests:
                if r.status not in TERMINAL:
                    actions.append(("cancel", r.rid))
        for dst in sorted(self.forked):
            actions.append(("free_fork", dst))
        if sched.cache is not None and sched.cache._page_node:
            actions.append(("evict",))
        return actions

    def apply(self, action: Tuple) -> None:
        getattr(self, "_do_" + action[0])(*action[1:])

    def _req(self, rid: int):
        return next(r for r in self.requests if r.rid == rid)

    def _do_admit(self) -> None:
        self.sched.admit()

    def _do_grow(self, rid: int) -> None:
        """One chunked-prefill installment (the engine's per-step cache)."""
        from repro.serving.request import Status

        req = self._req(rid)
        if self.sched.grow_prefill(req):
            req.prefill_pos = min(req.prefill_pos + self.sched.prefill_chunk,
                                  req.total_len)
            if req.prefill_pos >= req.total_len:
                req.status = Status.RUNNING

    def _do_decode(self) -> None:
        """One decode step: extend every running row, sample one token."""
        from repro.serving.request import Status

        self.sched.extend_for_decode()
        for req in list(self.sched.running.values()):
            if (req.status is Status.RUNNING
                    and len(req.output) < self.cfg.max_new):
                req.output.append(7)

    def _do_finish(self, rid: int) -> None:
        self.sched.finish(self._req(rid))

    def _do_cancel(self, rid: int) -> None:
        self.cancel_count += 1
        self.sched.cancel(self._req(rid))

    def _do_fail(self, rid: int) -> None:
        from repro.errors import EngineError

        self.fail_count += 1
        self.sched.fail(self._req(rid), EngineError("injected fault"))

    def _do_fork(self, src_rid: int) -> None:
        """Copy-on-write child row (no scheduler request — the model
        tracks the bare row so ``fork``'s all-or-nothing contract is
        checkable in isolation)."""
        dst = FORK_RID_BASE + self.fork_count
        self.fork_count += 1
        if self.sched.mgr.fork(src_rid, dst):
            self.forked = self.forked | {dst}

    def _do_free_fork(self, dst: int) -> None:
        self.sched.mgr.free(dst)
        self.forked = self.forked - {dst}

    def _do_evict(self) -> None:
        self.sched.cache.reclaim(1)

    # -- canonical state ------------------------------------------------
    def state_key(self) -> Tuple:
        """Hashable quotient of the full state.

        Two abstractions keep the space finite and small, both sound
        because the dynamics never inspect the quotiented detail:

        * **page renaming** — physical page ids are interchangeable
          (every operation treats them opaquely), so pages are
          renumbered in first-appearance order over a fixed
          serialization (rows by rid, forked rows, cache trie by token
          path, then the free list in stack order);
        * **LRU rank** — the cache clock grows without bound; only each
          node's *rank* in the (last_use, seq) order affects future
          eviction choices, so the rank replaces the absolute clock.
        """
        mgr = self.sched.mgr
        rename: Dict[int, int] = {}

        def pid(p: int) -> int:
            if p not in rename:
                rename[p] = len(rename)
            return rename[p]

        reqs = tuple(
            (r.rid, r.status.value, r.slot, r.prefill_pos, r.cached_prefix,
             len(r.output),
             tuple(pid(p) for p in mgr.tables.get(r.rid, ())),
             mgr.lens.get(r.rid, -1))
            for r in self.requests)
        forked = tuple(
            (d, tuple(pid(p) for p in mgr.tables.get(d, ())),
             mgr.lens.get(d, -1))
            for d in sorted(self.forked))
        cache_key: Tuple = ()
        if self.sched.cache is not None:
            nodes = sorted(self.sched.cache._page_node.values(),
                           key=lambda n: (n.last_use, n.seq))
            rank = {id(n): i for i, n in enumerate(nodes)}

            def path(n) -> Tuple:
                parts = []
                while n.parent is not None:
                    parts.append(n.chunk)
                    n = n.parent
                return tuple(reversed(parts))

            cache_key = tuple(
                (p, pid(page), rk) for p, page, rk in sorted(
                    (path(n), n.page, rank[id(n)]) for n in nodes))
        free = tuple(pid(p) for p in mgr.free_list)
        # refcounts of renamed pages in rename order, then the refcount
        # multiset of any page not reached by the serialization (a leaked
        # page is renaming-equivalent to any other leaked page)
        by_new = sorted(rename, key=rename.get)
        refs = tuple(mgr.refcount[p] for p in by_new)
        leaked = tuple(sorted(mgr.refcount[p] for p in range(mgr.num_pages)
                              if p not in rename))
        return (reqs, tuple(r.rid for r in self.sched.waiting), free,
                refs, leaked, forked, cache_key,
                self.fork_count, self.cancel_count, self.fail_count)

    # -- the invariants --------------------------------------------------
    def violations(self) -> List[str]:
        from repro.serving.request import TERMINAL
        from repro.serving.scheduler import LIVE

        mgr = self.sched.mgr
        out: List[str] = []
        live_rids = {r.rid for r in self.requests if r.status in LIVE}
        allowed = live_rids | set(self.forked)
        for rid in mgr.tables:
            if rid not in allowed:
                status = next((r.status.value for r in self.requests
                               if r.rid == rid), "untracked")
                out.append(
                    f"table row held by non-live rid {rid} (status "
                    f"{status}): its pages can alias a later reservation")
        occ = Counter(p for row in mgr.tables.values() for p in row)
        resident = (set(self.sched.cache._page_node)
                    if self.sched.cache is not None else set())
        for p in range(mgr.num_pages):
            expect = occ.get(p, 0) + (1 if p in resident else 0)
            if mgr.refcount[p] != expect:
                out.append(
                    f"page {p} refcount {mgr.refcount[p]} != "
                    f"{occ.get(p, 0)} table occurrences + "
                    f"{int(p in resident)} cache residency")
        free = mgr.free_list
        if len(set(free)) != len(free):
            out.append("free list holds duplicate pages")
        for p in free:
            if mgr.refcount[p] != 0:
                out.append(f"page {p} on the free list with refcount "
                           f"{mgr.refcount[p]}")
        held = sum(1 for p in range(mgr.num_pages) if mgr.refcount[p] > 0)
        if len(set(free)) + held != mgr.num_pages:
            out.append(f"free-list conservation broken: {len(set(free))} "
                       f"free + {held} referenced != {mgr.num_pages}")
        for rid, row in mgr.tables.items():
            want = -(-mgr.lens.get(rid, 0) // mgr.page_size)
            if len(row) != want:
                out.append(f"rid {rid} holds {len(row)} pages for "
                           f"{mgr.lens.get(rid, 0)} tokens (want {want})")
        for r in self.requests:
            if r.status in TERMINAL and r.slot != -1:
                out.append(f"terminal rid {r.rid} still owns slot "
                           f"{r.slot}")
        if (not self.forked
                and all(r.status in TERMINAL for r in self.requests)):
            if len(free) + len(resident) != mgr.num_pages:
                out.append(
                    "terminal-state leak: all requests terminal but "
                    f"{mgr.num_pages - len(free) - len(resident)} "
                    "page(s) neither free nor cache-resident")
        return out


# ---------------------------------------------------------------------------
# BFS over the bounded state space
# ---------------------------------------------------------------------------
@dataclass
class ExploreResult:
    states: int = 0
    capped: bool = False
    trace: Optional[List[str]] = None       # minimal counterexample
    violations: List[str] = field(default_factory=list)


def _fmt(action: Tuple) -> str:
    return action[0] if len(action) == 1 else \
        f"{action[0]}({', '.join(str(a) for a in action[1:])})"


def explore(make_driver, max_states: int = MAX_STATES) -> ExploreResult:
    """BFS every interleaving; the first violation (BFS order = fewest
    actions) is returned with its minimal trace."""
    from repro.errors import EngineError

    res = ExploreResult()
    root = make_driver()
    root_key = root.state_key()
    # key -> (parent_key, action) for minimal-trace reconstruction
    seen: Dict[Tuple, Optional[Tuple]] = {root_key: None}

    def trace_to(key: Tuple, last: Optional[Tuple]) -> List[str]:
        steps: List[Tuple] = [last] if last is not None else []
        while seen[key] is not None:
            parent_key, action = seen[key]
            steps.append(action)
            key = parent_key
        return [_fmt(a) for a in reversed(steps)]

    queue = deque([(root, root_key)])
    while queue:
        drv, key = queue.popleft()
        res.states += 1
        bad = drv.violations()
        if bad:
            res.trace = trace_to(key, None)
            res.violations = bad
            return res
        for action in drv.enabled():
            nxt = drv.clone()
            try:
                nxt.apply(action)
            except EngineError as e:
                # an invariant guard tripping mid-transition IS a
                # counterexample (e.g. a double free the relation allows)
                res.trace = trace_to(key, action)
                res.violations = [f"{type(e).__name__}: {e}"]
                return res
            nkey = nxt.state_key()
            if nkey in seen:
                continue
            if len(seen) >= max_states:
                res.capped = True
                return res
            seen[nkey] = (key, action)
            queue.append((nxt, nkey))
    return res


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------
_result_cache: Dict[Tuple[str, int], List[Tuple[str, str]]] = {}


def _run_cases(cases: Sequence[Tuple]) -> List[Tuple[str, str]]:
    """[(label, message)] for every configuration that fails its proof."""
    failures: List[Tuple[str, str]] = []
    for label, factory in cases:
        res = explore(factory)
        if res.capped:
            failures.append((label, f"model check '{label}' exceeded "
                             f"{MAX_STATES} states — tighten the bounded "
                             "configuration"))
        elif res.violations:
            failures.append((
                label,
                f"model check '{label}' found an invariant violation "
                f"after {res.states} states: {res.violations[0]} — "
                f"minimal trace: {' -> '.join(res.trace) or '<initial>'}"))
    return failures


def _live_cases() -> List[Tuple]:
    return [(cfg.name, (lambda c=cfg: LifecycleDriver(c)))
            for cfg in CONFIGS]


def _fixture_cases(ctx: FileContext) -> Optional[Sequence[Tuple]]:
    if not any(isinstance(s, ast.Assign) and len(s.targets) == 1
               and isinstance(s.targets[0], ast.Name)
               and s.targets[0].id == FIXTURE_CASES
               for s in ctx.tree.body):
        return None
    path = Path(ctx.path)
    if not path.is_absolute():
        path = Path.cwd() / path
    spec = importlib.util.spec_from_file_location(
        "_replint_statemachine_fixture", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)  # type: ignore[union-attr]
    return getattr(mod, FIXTURE_CASES)


@register(
    RULE,
    "bounded exhaustive model checking of the request lifecycle: BFS over "
    "every admit/grow/extend/preempt/fork/cancel/fail/finish/evict "
    "interleaving of small configurations, asserting refcount == table "
    "occurrences + cache residency, free-list conservation and terminal "
    "cleanliness at every reachable state",
    dirs=("serving",))
def check(ctx: FileContext, project: Project) -> List[Finding]:
    is_live = (ctx.path.startswith("src/")
               and ctx.path.endswith("serving/scheduler.py"))
    cache_key = (ctx.path, hash(ctx.source))
    if cache_key not in _result_cache:
        if is_live:
            cases = _live_cases()
        else:
            cases = _fixture_cases(ctx)
            if cases is None:
                return []
        _result_cache[cache_key] = _run_cases(cases)
    return [Finding(rule=RULE, path=ctx.path, line=1, col=0, symbol=label,
                    message=message)
            for label, message in _result_cache[cache_key]]
