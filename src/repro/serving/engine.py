"""Serving engine: continuous batching over the paged KV cache.

The engine is the paper's "system-level integration" (§III): the model's
prefill/decode steps run against *global* K/V page pools, the scheduler's
host-side page manager decides admission/preemption, and block tables flow
device-side each step (the asynchronous-update contract of DESIGN.md §2).

One Engine instance serves one model on one batch of ``max_slots`` logical
slots. The pool is deliberately *oversubscribable*: ``pool_tokens`` may be
far less than ``max_slots × max_seq_len`` — that is the paper's entire
memory win over max-length pre-allocation.

The contiguous baseline (``paged=False``) allocates the paper's comparison
target instead: per-slot max-length buffers.

Every phase of a step runs inside a host span (``TraceAnnotation``, named
``engine.*``; README "Tracing a step").  A span costs about a microsecond
when no profiler runs; under one it lands on the host plane, on the clock
of the device's program and op lines, so a device idle gap is named by
the host work in flight.  Spans open only in host code: inside a jitted
function one would fire only while it is traced.
"""

from __future__ import annotations

import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.base import ModelConfig
from repro.core.paging import HostPageManager
from repro.core.prefix_cache import PrefixCache
from repro.errors import (DeadlineExceeded, EngineConfigError,
                          EngineError, InternalError,
                          InvalidRequest, NumericsError, PoolExhausted,
                          RequestTooLong, SchedulerInvariantError,
                          TransientDeviceError)
from repro.models.api import build_model
from repro.models.attention import cross_gate
from repro.serving.faults import FaultPlan, FaultyPageManager
from repro.serving.request import Request, Status
from repro.serving.sampler import SampleParams, sample, validate_sample_params
from repro.serving.scheduler import Scheduler


class Engine:
    def __init__(
        self,
        cfg: ModelConfig,
        params: Any = None,
        *,
        max_slots: int = 8,
        max_seq_len: int = 512,
        pool_tokens: Optional[int] = None,  # None => slots*max_seq_len (no oversub)
        paged: Optional[bool] = None,
        impl: str = "ref",
        rng: Optional[jax.Array] = None,
        dtype=jnp.float32,
        interpret: Optional[bool] = None,  # None → auto (off-TPU: interpret)
        pages_per_block: Optional[int] = None,  # decode kernel knobs;
        num_splits: Optional[int] = None,  # None → auto-tuned per shape
        combine_mode: Optional[str] = None,  # split-K merge impl (None=auto)
        backend: Optional[str] = None,  # kernel lowering: "tpu" | "gpu"
        # (None → auto from jax.default_backend(); CPU hosts fall back to
        # the TPU lowering in interpret mode)
        prefill_chunk: Optional[int] = None,  # tokens of prompt prefilled
        # per engine step (None = whole prompt in one monolithic pass).
        # Chunked prefill bounds per-step work: the whole prefill
        # sub-batch caches at most `prefill_chunk` tokens per iteration
        # (a *global* budget split across concurrent prefills),
        # interleaved with decode steps for the running batch
        # (vLLM-style continuous batching), resuming from the
        # already-cached prefix pages each step.
        prefix_cache: bool = False,  # global prefix cache: radix-indexed
        # page sharing across requests (core.prefix_cache).  Admission
        # attaches new prompts to the longest previously-cached prefix
        # (zero prefill work for the hit), releases retain written pages,
        # and the pool evicts detached chains LRU-first under pressure.
        # Requires the paged engine with pure dense self-attention (no
        # windowed/recurrent/cross layers — see the gates below).
        # --- fault tolerance (ISSUE 6) --------------------------------
        faults: Optional[FaultPlan] = None,  # deterministic fault
        # injection: wraps the page manager's reserve/extend/free, the
        # prefill/decode dispatch, and per-request sampling rows
        numerics_guard: bool = True,  # detect NaN/Inf logits per row and
        # fail *that* request (the rest of the batch keeps decoding)
        max_waiting: Optional[int] = None,  # bounded wait queue
        # (reject-on-full with Backpressure); None = unbounded
        admit_watermark: Optional[float] = None,  # pool-utilization
        # fraction above which new admits are shed with Backpressure
        # instead of admitted into preemption thrash; None = off
        max_step_retries: int = 3,  # transient-device retries per dispatch
        retry_backoff_s: float = 0.0,  # base backoff (doubles per retry;
        # 0 = no sleep — deterministic tests)
    ):
        self.cfg = cfg
        self.model = build_model(cfg)
        self.impl = impl
        self.interpret = interpret
        self.pages_per_block = pages_per_block
        self.num_splits = num_splits
        self.combine_mode = combine_mode
        self.backend = backend
        self.dtype = dtype
        self.max_slots = max_slots
        self.max_seq_len = max_seq_len
        self.paged = cfg.paged_attention if paged is None else paged
        self.prefill_chunk = prefill_chunk
        if prefill_chunk is not None:
            if prefill_chunk < 1:
                raise EngineConfigError(
                    "prefill_chunk must be >= 1 (or None)",
                    prefill_chunk=prefill_chunk)
            if not self.paged:
                raise EngineConfigError(
                    "chunked prefill requires the paged engine (paged=True)",
                    prefill_chunk=prefill_chunk)
            codes = cfg.pattern() if cfg.family != "encdec" else ""
            if any(c in "RMS" for c in codes):
                raise EngineConfigError(
                    "chunked prefill does not support recurrent layers "
                    f"(pattern {cfg.layer_pattern!r}): their prefill "
                    "state replay assumes the whole prompt",
                    pattern=cfg.layer_pattern)
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        self.rng, init_rng = jax.random.split(rng)
        self.params = (params if params is not None
                       else self.model.init_params(init_rng, dtype))

        ps = cfg.page_size
        window = getattr(self.model, "window", 0)
        codes = cfg.pattern() if cfg.family != "encdec" else "A"
        # ring-sized tables are only sound when EVERY attention layer is
        # windowed: a mixed dense/windowed pattern's 'A' layers carry live
        # KV for the whole sequence, so their table must span max_seq_len
        # (the 'W' layers keep using columns 0..ring-1 as the ring).
        self._ring_tables = window > 0 and "A" not in codes
        if self._ring_tables:
            self.pages_per_seq = -(-window // ps) + 1
        elif window > 0:
            self.pages_per_seq = max(-(-max_seq_len // ps),
                                     -(-window // ps) + 1)
        else:
            self.pages_per_seq = -(-max_seq_len // ps)
        if pool_tokens is None:
            num_pages = max_slots * self.pages_per_seq
        else:
            num_pages = max(-(-pool_tokens // ps), self.pages_per_seq)
        self.num_pages = num_pages

        if prefix_cache:
            # pages must be immutable once written for cross-request
            # sharing to be sound, and their content must be a function
            # of the token prefix alone (that is the radix key)
            if not self.paged:
                raise EngineConfigError(
                    "prefix_cache requires the paged engine (paged=True)")
            if window > 0:
                raise EngineConfigError(
                    "prefix_cache requires window=0: windowed layers "
                    "overwrite their ring pages in place, so cached "
                    "pages shared from a live donor would be mutated",
                    window=window)
            if (cfg.family == "encdec"
                    or getattr(self.model, "n_cross_layers", 0)):
                raise EngineConfigError(
                    "prefix_cache does not support encoder/cross-"
                    "attention models: self-attention K/V depend on the "
                    "per-request image/audio context, so token-keyed "
                    "page sharing would be wrong", family=cfg.family)
            if any(c in "RMS" for c in cfg.pattern()):
                raise EngineConfigError(
                    "prefix_cache does not support recurrent layers "
                    f"(pattern {cfg.layer_pattern!r}): their state is "
                    "not page-addressed", pattern=cfg.layer_pattern)

        self.faults = faults
        self.numerics_guard = numerics_guard
        self.max_step_retries = max_step_retries
        self.retry_backoff_s = retry_backoff_s
        self.mgr = (FaultyPageManager(num_pages, ps, faults)
                    if faults is not None else HostPageManager(num_pages, ps))
        self.prefix_cache = (PrefixCache(self.mgr, faults=faults)
                             if prefix_cache else None)
        self.scheduler = Scheduler(self.mgr, max_slots, max_seq_len,
                                   prefill_chunk=prefill_chunk,
                                   max_waiting=max_waiting,
                                   admit_watermark=admit_watermark,
                                   prefix_cache=self.prefix_cache)
        self.state = self._init_state()
        self._slot_extra: Dict[int, Dict] = {}
        self.steps = 0
        self.stats: Dict[str, int] = {"transient_retries": 0,
                                      "first_tokens": 0, "prefill_ns": 0,
                                      "chunk_steps": 0, "chunk_traces": 0}
        self._jit_decode = jax.jit(self._decode_fn, static_argnames=())
        self._jit_chunk = jax.jit(self._chunk_fn,
                                  static_argnames=("cross_mode",))

    # ------------------------------------------------------------------
    def _init_state(self) -> Dict:
        cfg, m = self.cfg, self.model
        B, ps = self.max_slots, cfg.page_size
        Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        st: Dict[str, Any] = {"pos": jnp.zeros((B,), jnp.int32)}
        n_attn = getattr(m, "n_attn_layers", 0)
        if n_attn:
            if self.paged:
                pool = (n_attn, self.num_pages, Hkv, ps, hd)
                pool_dt = (jnp.int8 if cfg.kv_dtype == "int8"
                           else self.dtype)
                st["k_pages"] = jnp.zeros(pool, pool_dt)
                st["v_pages"] = jnp.zeros(pool, pool_dt)
                st["tables"] = jnp.full((B, 1, self.pages_per_seq), -1,
                                        jnp.int32)
            else:
                # the paper's baseline: contiguous max-length per-slot buffers
                buf = (n_attn, B, self.max_seq_len, Hkv, hd)
                st["k_buf"] = jnp.zeros(buf, self.dtype)
                st["v_buf"] = jnp.zeros(buf, self.dtype)
        n_cross = getattr(m, "n_cross_layers", 0)
        if cfg.family == "encdec":
            n_cross = cfg.n_layers
        if n_cross:
            ctx_len = (cfg.n_audio_frames if cfg.family == "encdec"
                       else cfg.n_image_tokens)
            ck = (n_cross, B, ctx_len, Hkv, hd)
            st["cross_k"] = jnp.zeros(ck, self.dtype)
            st["cross_v"] = jnp.zeros(ck, self.dtype)
        # recurrent state slots
        from repro.models import rglru, ssm
        rec: Dict[str, Any] = {}
        codes = cfg.pattern() if cfg.family != "encdec" else ""
        for code, init in (("R", rglru.rglru_init_state),
                           ("M", ssm.mlstm_init_state),
                           ("S", ssm.slstm_init_state)):
            n = sum(c == code for c in codes)
            if n:
                one = init(B, cfg, self.dtype)
                rec[code] = jax.tree_util.tree_map(
                    lambda a: jnp.zeros((n,) + a.shape, a.dtype), one)
        if rec:
            st["rec"] = rec
        return st

    # ------------------------------------------------------------------
    def add_request(self, req: Request, extra: Optional[Dict] = None) -> int:
        """Validate and enqueue ``req``.

        Raises structured errors before the request holds any resources:
        ``InvalidRequest`` (bad sampling params), ``RequestTooLong``
        (prompt + budget exceeds max_seq_len), or ``Backpressure`` (wait
        queue full / pool above the admission high-watermark — carries a
        retry hint; resubmit later).
        """
        validate_sample_params(req)
        if req.prompt_len + req.max_new_tokens > self.max_seq_len:
            raise RequestTooLong(
                f"request exceeds engine max_seq_len: prompt_len "
                f"{req.prompt_len} + max_new_tokens {req.max_new_tokens} > "
                f"{self.max_seq_len}", rid=req.rid,
                limit=self.max_seq_len)
        req.metrics["t_arrive"] = time.perf_counter()
        req.metrics["step_arrive"] = self.steps
        if extra is not None:
            req.metrics["_extra"] = extra  # modality stub embeddings
        self.scheduler.add(req)  # may raise Backpressure (nothing held yet)
        return req.rid

    def generate(self, reqs: List[Request],
                 extras: Optional[List[Optional[Dict]]] = None,
                 max_steps: int = 100_000) -> List[Request]:
        """Blocking helper: run until the given requests all reach a
        terminal state.  Raises ``DeadlineExceeded`` when ``max_steps``
        engine steps leave any of them unfinished."""
        extras = extras or [None] * len(reqs)
        for r, e in zip(reqs, extras):
            self.add_request(r, e)
        for _ in range(max_steps):
            if all(r.done for r in reqs):
                return reqs
            self.step()
        if not all(r.done for r in reqs):
            raise DeadlineExceeded(
                f"generate: {sum(not r.done for r in reqs)} of {len(reqs)} "
                f"requests unfinished after max_steps={max_steps}",
                max_steps=max_steps)
        return reqs

    # ------------------------------------------------------------------
    def step(self) -> List[Request]:
        """One engine iteration: deadlines → admit → prefill → decode →
        sample → finish.

        Monolithic mode (``prefill_chunk=None``) prefills every admitted
        prompt whole.  Chunked mode interleaves: each PREFILLING request
        caches one ``prefill_chunk``-token installment (resuming from its
        cached pages) and the RUNNING sub-batch decodes one token — both
        sub-batches advance in the same iteration, so no step's cost
        scales with a full prompt length.  Sampling fires only when a
        request's *last* chunk lands.

        Fault isolation contract (gated by ``tests/test_faults.py``):
        failures attributable to one request (NaN logits, deadline miss,
        allocation starvation with no recourse) fail *that* request —
        pages released, batch-mates unaffected; transient device errors
        on a dispatch are retried with backoff; anything unstructured is
        wrapped in ``InternalError``.  No bare exception escapes.

        Returns requests that reached a terminal state this step
        (FINISHED and FAILED; cancellations report via cancel_request).
        """
        try:
            return self._step_impl()
        except EngineError:
            raise  # structured: the caller can route it
        except Exception as e:  # noqa: BLE001 — the wrap IS the contract
            raise InternalError(
                f"unstructured failure escaped engine step: {e!r}") from e

    def _step_impl(self) -> List[Request]:
        with TraceAnnotation("engine.step"):
            self.steps += 1
            with TraceAnnotation("engine.sched.admit"):
                self.scheduler.check_deadlines(self.steps)
                admitted = self.scheduler.admit()
            finished: List[Request] = []
            if self.prefill_chunk is None:
                if admitted:
                    self._dispatch("prefill", self._prefill, admitted)
                    # prefill's sampled token may already hit EOS / max_new
                    finished += self._finish_done()
            elif any(r.status is Status.PREFILLING
                     for r in self.scheduler.running.values()):
                self._dispatch("prefill", self._prefill_chunk_step)
                finished += self._finish_done()
            if any(r.status is Status.RUNNING
                   for r in self.scheduler.running.values()):
                if self.paged:
                    with TraceAnnotation("engine.sched.extend"):
                        self.scheduler.extend_for_decode()
                # extend may have failed the last decoder (starvation) —
                # re-check before dispatching an empty decode sub-batch
                if any(r.status is Status.RUNNING
                       for r in self.scheduler.running.values()):
                    self._dispatch("decode", self._decode)
                    finished += self._finish_done()
            finished += self._drain_failed()
            return finished

    def _dispatch(self, site: str, fn, *args):
        """Run a prefill/decode dispatch with transient-fault retries.

        The fault plan's transient site fires *before* ``fn`` mutates any
        state, so a retry re-runs the dispatch from scratch — the same
        recovery a real transient device error at launch time gets.
        Backoff doubles per attempt from ``retry_backoff_s`` (0 = no
        sleep); after ``max_step_retries`` the structured error escapes.
        """
        delay = self.retry_backoff_s
        for attempt in range(self.max_step_retries + 1):
            try:
                if (self.faults is not None
                        and self.faults.fire(site) == "transient"):
                    raise TransientDeviceError(
                        f"injected transient device error at {site} "
                        "dispatch", site=site, attempt=attempt)
                return fn(*args)
            except TransientDeviceError:
                self.stats["transient_retries"] += 1
                if attempt >= self.max_step_retries:
                    raise
                if delay:
                    time.sleep(delay)
                    delay *= 2

    def _drain_failed(self) -> List[Request]:
        """Collect requests failed mid-step (deadline, starvation, NaN
        guard) so ``step`` reports every terminal transition it caused."""
        with TraceAnnotation("engine.sched.finish"):
            ev, self.scheduler.failed_events = (
                self.scheduler.failed_events, [])
            now = time.perf_counter()
            for r in ev:
                r.metrics.setdefault("t_done", now)
            return ev

    # ------------------------------------------------------------------
    def cancel_request(self, rid: int) -> bool:
        """Tear down request ``rid`` in any state: WAITING (dequeued),
        PREFILLING mid-chunk or stalled-on-dry-pool (pages + table row
        released; no ghost row reaches the next decode sub-batch),
        RUNNING (slot + pages released mid-decode), PREEMPTED (dequeued).
        Returns False for unknown or already-terminal requests.  Safe
        between steps — cancellation never disturbs batch-mates.
        """
        req = self._find_request(rid)
        if req is None:
            return False
        if not self.scheduler.cancel(req):
            return False
        req.metrics.setdefault("t_done", time.perf_counter())
        return True

    def _find_request(self, rid: int) -> Optional[Request]:
        for r in self.scheduler.waiting:
            if r.rid == rid:
                return r
        for r in self.scheduler.running.values():
            if r.rid == rid:
                return r
        return None

    def robustness_report(self) -> Dict[str, int]:
        """Counters for the failure surface (mirrors memory_report)."""
        s = self.scheduler
        pc = self.prefix_cache
        return {
            "failed": s.failed,
            "cancelled": s.cancelled,
            "shed": s.shed,
            "deadline_misses": s.deadline_misses,
            "preempted": s.preempted,
            "prefill_stalls": s.prefill_stalls,
            "transient_retries": self.stats["transient_retries"],
            "fault_fires": self.faults.fires if self.faults else 0,
            # prefix-cache hit surface (all 0 when the cache is off)
            "prefix_hits": pc.hits if pc else 0,
            "prefix_misses": pc.misses if pc else 0,
            "prefix_hit_tokens": pc.hit_tokens if pc else 0,
            "prefix_evicted_pages": pc.evicted_pages if pc else 0,
            # where a request's time to first token goes: queued for a
            # slot (first admissions), then admission -> first token
            "admitted": s.admitted,
            "queue_wait_ns": s.queue_wait_ns,
            "first_tokens": self.stats["first_tokens"],
            "prefill_ns": self.stats["prefill_ns"],
            # the compiled chunk step: dispatches, and times it was traced
            # (one per engine, a few more for cross-attention models)
            "chunk_steps": self.stats["chunk_steps"],
            "chunk_traces": self.stats["chunk_traces"],
        }

    # ------------------------------------------------------------------
    def _tables_array(self, decode: bool = False) -> jnp.ndarray:
        """Block tables for the batch, one row per live slot.

        ``decode=True`` blanks PREFILLING slots (their rows stay -1): the
        decode pass must neither write its placeholder token into, nor
        attend over, a half-prefilled sequence's pages.

        A dense sequence whose page row outgrows the device table width is
        a hard error — silently truncating ``row[:pages_per_seq]`` would
        drop the KV tail and produce wrong output with no signal.
        (Pure-windowed models are the exception by design: their row is a
        ring and ``row[:ring]`` IS the table — ring slots are overwritten
        in place, so extra host-side pages never carry live data.  Mixed
        dense/windowed patterns get a full-width table and no exemption.)
        """
        with TraceAnnotation("engine.tables"):
            t = np.full((self.max_slots, 1, self.pages_per_seq), -1,
                        np.int32)
            windowed = self._ring_tables
            for slot, req in self.scheduler.running.items():
                if decode and req.status is not Status.RUNNING:
                    continue
                row = self.mgr.tables.get(req.rid, [])
                if len(row) > self.pages_per_seq and not windowed:
                    raise SchedulerInvariantError(
                        f"request {req.rid} holds {len(row)} pages but the "
                        f"device block table is {self.pages_per_seq} pages "
                        f"wide (max_seq_len={self.max_seq_len}); the "
                        f"sequence outgrew the engine — refusing to "
                        f"truncate its KV tail silently")
                t[slot, 0, :len(row)] = row[:self.pages_per_seq]
            return jnp.asarray(t)

    def _prefill(self, admitted: List[Tuple[int, Request]]) -> None:
        """Prefill newly admitted requests (sub-batch padded to max len)."""
        cfg = self.cfg
        slots = [s for s, _ in admitted]
        reqs = [r for _, r in admitted]
        if any(r.prefill_pos > 0 for r in reqs):
            # at least one row attached to cached prefix pages: run the
            # wave through the prefix-aware chunk kernel, each row's
            # suffix only (cold rows are just q_start=0)
            self._prefill_from(slots, reqs)
            return
        with TraceAnnotation("engine.prefill.prep"):
            # preempted requests re-prefill prompt + generated so far
            toks = [r.prompt + r.output for r in reqs]
            L = max(len(t) for t in toks)
            B = len(reqs)
            batch = np.zeros((B, L), np.int32)
            lens = np.zeros((B,), np.int32)
            for i, t in enumerate(toks):
                batch[i, :len(t)] = t
                lens[i] = len(t)

            # sub-batch tables for the admitted slots
            full_tables = self._tables_array()
            sub_tables = full_tables[np.asarray(slots), 0]

            st = self.state
            sub_state: Dict[str, Any] = {"pos": jnp.asarray(lens)}
            if self.paged and "k_pages" in st:
                sub_state["k_pages"] = st["k_pages"]
                sub_state["v_pages"] = st["v_pages"]
                sub_state["tables"] = sub_tables
            extra = self._collect_extra(reqs)
        if not self.paged:
            self._prefill_contiguous(slots, batch, lens, extra, reqs)
            return

        with TraceAnnotation("engine.prefill.model"):
            logits, new_st = self.model.prefill(
                self.params, jnp.asarray(batch), sub_state,
                lens=jnp.asarray(lens), extra=extra, impl=self.impl)

        # merge: global pools were written in place (scatter by tables);
        # per-slot states (pos, cross, rec) land in the admitted slots.
        with TraceAnnotation("engine.prefill.merge"):
            if "k_pages" in new_st:
                st["k_pages"] = new_st["k_pages"]
                st["v_pages"] = new_st["v_pages"]
            idx = jnp.asarray(slots)
            st["pos"] = st["pos"].at[idx].set(jnp.asarray(lens))
            for key in ("cross_k", "cross_v"):
                if key in new_st:
                    st[key] = st[key].at[:, idx].set(new_st[key])
            if "rec" in new_st:
                st["rec"] = jax.tree_util.tree_map(
                    lambda g, s: g.at[:, idx].set(s), st["rec"],
                    new_st["rec"])

            for i, r in enumerate(reqs):
                r.prefill_pos = int(lens[i])  # everything written
        self._cache_insert_live(reqs)
        self._sample_and_append(reqs, logits, first=True)

    def _prefill_from(self, slots: List[int], reqs: List[Request]) -> None:
        """Monolithic prefill resuming past cached prefixes: each row runs
        only its un-cached suffix (``q_start = matched tokens``) through
        the prefix-aware chunk kernel, attending back over the shared
        pages through its block table.  Output must match a cold
        ``model.prefill`` of the whole prompt ≤ 1e-5 — that equivalence
        is exactly what the chunked-prefill gate already proves for the
        kernel, and ``tests/test_prefix_cache.py`` re-proves end-to-end.

        Only reachable with the prefix cache on, which gates the model to
        pure dense self-attention — no cross/rec state to merge here.
        """
        with TraceAnnotation("engine.prefill.prep"):
            toks = [r.prompt + r.output for r in reqs]
            starts = np.asarray([r.prefill_pos for r in reqs], np.int32)
            lens = np.asarray([len(t) for t in toks], np.int32)
            q_lens = lens - starts  # >= 1: attach caps the match at total-1
            B, C = len(reqs), int(q_lens.max())
            batch = np.zeros((B, C), np.int32)
            for i, t in enumerate(toks):
                batch[i, :q_lens[i]] = t[starts[i]:lens[i]]

            full_tables = self._tables_array()
            sub_tables = np.asarray(full_tables)[np.asarray(slots)]
            st = self.state
            sub_state: Dict[str, Any] = {
                "pos": jnp.asarray(starts),
                "k_pages": st["k_pages"],
                "v_pages": st["v_pages"],
                "tables": jnp.asarray(sub_tables),
            }
        with TraceAnnotation("engine.prefill.model"):
            logits, new_st = self.model.prefill_chunk(
                self.params, jnp.asarray(batch), sub_state,
                q_start=jnp.asarray(starts), q_lens=jnp.asarray(q_lens),
                impl=self.impl, interpret=self.interpret,
                pages_per_block=self.pages_per_block,
                num_splits=self.num_splits, combine_mode=self.combine_mode,
                backend=self.backend)

        with TraceAnnotation("engine.prefill.merge"):
            st["k_pages"] = new_st["k_pages"]
            st["v_pages"] = new_st["v_pages"]
            idx = jnp.asarray(slots)
            st["pos"] = st["pos"].at[idx].set(jnp.asarray(lens))
            for i, r in enumerate(reqs):
                r.prefill_pos = int(lens[i])
        self._cache_insert_live(reqs)
        self._sample_and_append(reqs, logits, first=True)

    def _cache_insert_live(self, reqs: List[Request]) -> None:
        """Index each request's written full pages into the prefix cache
        (progressive insert: concurrent requests sharing a prompt head
        hit on each other's pages mid-wave, not just after release).
        Callers update ``req.prefill_pos`` to the written token count
        first — partial pages are skipped inside ``insert``."""
        if self.prefix_cache is None:
            return
        with TraceAnnotation("engine.prefix.insert"):
            for r in reqs:
                row = self.mgr.tables.get(r.rid)
                if row:
                    self.prefix_cache.insert(r.prompt + r.output, row,
                                             r.prefill_pos)

    def _prefill_chunk_step(self) -> None:
        """Advance every PREFILLING request by one ``prefill_chunk``
        installment (chunked continuous batching).

        The ``prefill_chunk`` token budget is **global across the prefill
        sub-batch**: k concurrent PREFILLING rows split one chunk (oldest
        slot first), they do not each cache a full chunk — the former
        per-request budget let a step's prefill work scale as
        ``k * prefill_chunk``, defeating the bounded-per-step-work
        contract the knob exists for.  Each selected installment is
        reserved chunk-wise (`Scheduler.grow_prefill`); a request whose
        installment cannot get pages stalls this step and resumes from
        its cached pages (``mgr.lens``) later — no recompute.  When a
        request's last chunk lands it flips to RUNNING and its first
        token is sampled from the chunk's last-position logits.
        """
        chunk = self.prefill_chunk
        budget = chunk  # global per-step token budget, split across rows
        sel: List[Tuple[int, Request, int, int]] = []
        with TraceAnnotation("engine.sched.extend"):
            for slot in sorted(self.scheduler.running):
                if budget <= 0:
                    break
                # re-fetch per iteration: grow_prefill below may preempt a
                # PREFILLING victim in a slot this (snapshotted) loop has
                # not visited yet — indexing the snapshot would KeyError
                req = self.scheduler.running.get(slot)
                if req is None or req.status is not Status.PREFILLING:
                    continue
                want = min(budget, req.total_len - req.prefill_pos)
                if not self.scheduler.grow_prefill(req, want):
                    continue  # stalled: keeps pages, resumes next step
                start = req.prefill_pos
                q_len = min(want, req.total_len - start)
                sel.append((slot, req, start, q_len))
                budget -= q_len
            # grow_prefill may preempt victims already selected — drop them
            sel = [(s, r, st0, ql) for (s, r, st0, ql) in sel
                   if self.scheduler.running.get(s) is r]
        if not sel:
            return
        with TraceAnnotation("engine.prefill.prep"):
            # fixed (max_slots, prefill_chunk) sub-batch shape: padding
            # rows are dead (tables -1, q_lens 0) so every chunk step runs
            # the one compiled program — ragged final chunks and varying
            # batch occupancy trace nothing new on the serving hot path
            C = chunk
            B = self.max_slots
            batch = np.zeros((B, C), np.int32)
            q_lens = np.zeros((B,), np.int32)
            starts = np.zeros((B,), np.int32)
            slots = [s for s, _, _, _ in sel]
            reqs = [r for _, r, _, _ in sel]
            for i, (_, req, st0, ql) in enumerate(sel):
                seq = req.prompt + req.output
                batch[i, :ql] = seq[st0:st0 + ql]
                starts[i] = st0
                q_lens[i] = ql
            # padding rows pose as resumes (q_start=1, q_lens=0): they are
            # dead either way, but must not look like first chunks — a row
            # at chunk 0 forces the model to recompute cross-attention K/V
            starts[len(sel):] = 1

            full_tables = self._tables_array()
            sub_tables = np.full((B,) + full_tables.shape[1:], -1, np.int32)
            sub_tables[:len(slots)] = np.asarray(full_tables)[
                np.asarray(slots)]

            st = self.state
            sub_state: Dict[str, Any] = {
                "pos": jnp.asarray(starts),
                "k_pages": st["k_pages"],
                "v_pages": st["v_pages"],
                "tables": jnp.asarray(sub_tables),
            }
            for key in ("cross_k", "cross_v"):
                if key in st:
                    # resume rows reuse their cached cross-K/V (the model
                    # skips the encoder/projection when no row is at
                    # chunk 0)
                    sub = np.zeros(
                        (st[key].shape[0], B) + st[key].shape[2:],
                        st[key].dtype)
                    sub[:, :len(slots)] = np.asarray(st[key])[
                        :, np.asarray(slots)]
                    sub_state[key] = jnp.asarray(sub)
            extra = self._collect_extra(reqs, pad_to=B)
            # the rows' starts are host ints: the cross-attention gate is
            # decided here and handed to the program (static mode)
            gate = cross_gate(starts) if "cross_k" in sub_state else {}
        with TraceAnnotation("engine.prefill.model"):
            self.stats["chunk_steps"] += 1
            logits, new_st = self._jit_chunk(
                self.params, jnp.asarray(batch), sub_state,
                jnp.asarray(starts), jnp.asarray(q_lens), extra, **gate)

        with TraceAnnotation("engine.prefill.merge"):
            st["k_pages"] = new_st["k_pages"]
            st["v_pages"] = new_st["v_pages"]
            idx = jnp.asarray(slots)
            live = np.arange(len(slots))
            st["pos"] = st["pos"].at[idx].set(
                jnp.asarray((starts + q_lens)[live]))
            for key in ("cross_k", "cross_v"):
                if key in new_st:
                    st[key] = st[key].at[:, idx].set(new_st[key][:, live])

            done_rows, done_reqs = [], []
            for i, (_, req, st0, ql) in enumerate(sel):
                req.prefill_pos = st0 + ql
                if req.prefill_pos >= req.total_len:  # last chunk landed
                    req.status = Status.RUNNING
                    done_rows.append(i)
                    done_reqs.append(req)
            if done_reqs:
                done_logits = jnp.asarray(logits)[np.asarray(done_rows)]
        self._cache_insert_live(reqs)
        if done_reqs:
            self._sample_and_append(done_reqs, done_logits, first=True)

    def _prefill_contiguous(self, slots, batch, lens, extra, reqs):
        """Baseline prefill: run forward, copy K/V into max-length buffers."""
        # teacher-forced forward to get K/V per layer is implicit: reuse the
        # paged prefill with identity tables into a temporary exact-size pool,
        # then gather into the contiguous buffers.
        cfg = self.cfg
        B, L = batch.shape
        ps = cfg.page_size
        pp = -(-L // ps)
        n_attn = getattr(self.model, "n_attn_layers", 0)
        tmp_tables = jnp.arange(B * pp, dtype=jnp.int32).reshape(B, pp)
        Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        tmp_state: Dict[str, Any] = {
            "pos": jnp.asarray(lens),
            "k_pages": jnp.zeros((n_attn, B * pp, Hkv, ps, hd), self.dtype),
            "v_pages": jnp.zeros((n_attn, B * pp, Hkv, ps, hd), self.dtype),
            "tables": tmp_tables,
        }
        with TraceAnnotation("engine.prefill.model"):
            logits, new_st = self.model.prefill(
                self.params, jnp.asarray(batch), tmp_state,
                lens=jnp.asarray(lens), extra=extra, impl=self.impl)
        from repro.core.cache import gather_layer
        with TraceAnnotation("engine.prefill.merge"):
            idx = jnp.asarray(slots)
            st = self.state
            for li in range(n_attn):
                k, v = gather_layer(new_st["k_pages"][li],
                                    new_st["v_pages"][li], tmp_tables, L)
                st["k_buf"] = st["k_buf"].at[li, idx, :L].set(k)
                st["v_buf"] = st["v_buf"].at[li, idx, :L].set(v)
            st["pos"] = st["pos"].at[idx].set(jnp.asarray(lens))
            for key in ("cross_k", "cross_v"):
                if key in new_st:
                    st[key] = st[key].at[:, idx].set(new_st[key])
            if "rec" in new_st:
                st["rec"] = jax.tree_util.tree_map(
                    lambda g, s: g.at[:, idx].set(s), st["rec"],
                    new_st["rec"])
        self._sample_and_append(reqs, logits, first=True)

    def _collect_extra(self, reqs: List[Request],
                       pad_to: Optional[int] = None) -> Optional[Dict]:
        extras = [r.metrics.get("_extra") for r in reqs]
        if pad_to is not None:
            extras += [None] * (pad_to - len(extras))
        if not any(e for e in extras):
            return None
        keys = next(e for e in extras if e).keys()
        out = {}
        for k in keys:
            parts = []
            for e in extras:
                if e is None or k not in e:
                    ref = next(x for x in extras if x and k in x)[k]
                    parts.append(np.zeros_like(np.asarray(ref)))
                else:
                    parts.append(np.asarray(e[k]))
            out[k] = jnp.asarray(np.stack(parts))
        return out

    # ------------------------------------------------------------------
    def _chunk_fn(self, params, tokens, state, q_start, q_lens, extra,
                  cross_mode="full", first_rows=None):
        """The model part of a chunk step, jitted as ``_jit_chunk``: one
        program at the fixed ``(max_slots, prefill_chunk)`` shape."""
        self.stats["chunk_traces"] += 1  # the body runs only when traced
        return self.model.prefill_chunk(
            params, tokens, state, q_start=q_start, q_lens=q_lens,
            extra=extra, cross_mode=cross_mode, first_rows=first_rows,
            impl=self.impl, interpret=self.interpret,
            pages_per_block=self.pages_per_block, num_splits=self.num_splits,
            combine_mode=self.combine_mode, backend=self.backend)

    def _decode_fn(self, params, tokens, state):
        return self.model.decode_step(
            params, tokens, state, impl=self.impl, interpret=self.interpret,
            pages_per_block=self.pages_per_block, num_splits=self.num_splits,
            combine_mode=self.combine_mode, backend=self.backend)

    def _decode(self) -> None:
        with TraceAnnotation("engine.decode.prep"):
            st = dict(self.state)
            if self.paged and "k_pages" in st:
                # decode=True blanks PREFILLING slots: their pages must not
                # receive the placeholder token's K/V nor be attended over
                st["tables"] = self._tables_array(decode=True)
            tokens = np.zeros((self.max_slots,), np.int32)
            live = np.zeros((self.max_slots,), bool)
            reqs: List[Optional[Request]] = [None] * self.max_slots
            for slot, req in self.scheduler.running.items():
                if req.status is not Status.RUNNING:
                    continue  # mid-prefill: not in the decode sub-batch
                seq = req.prompt + req.output
                tokens[slot] = seq[-1]
                live[slot] = True
                reqs[slot] = req

        with TraceAnnotation("engine.decode.launch"):
            if self.paged or "k_buf" not in st:
                logits, new_st = self._jit_decode(self.params,
                                                  jnp.asarray(tokens), st)
            else:
                logits, new_st = self._decode_contiguous(
                    jnp.asarray(tokens), st)
        with TraceAnnotation("engine.decode.merge"):
            # dead slots keep their old pos (decode bumps everyone's)
            mask = jnp.asarray(live)
            new_st["pos"] = jnp.where(mask, new_st["pos"],
                                      self.state["pos"])
            if self.paged and "tables" in new_st:
                new_st.pop("tables")  # host-owned, rebuilt each step
            self.state.update(new_st)
            live_reqs = [r for r in reqs if r is not None]
            live_logits = jnp.asarray(logits)[np.where(live)[0]]
        self._sample_and_append(live_reqs, live_logits, first=False)

    def _decode_contiguous(self, tokens, st):
        """Baseline decode path (contiguous buffers, family=dense-ish only)."""
        from repro.models import attention as mattn, layers
        cfg = self.cfg
        m = self.model
        params = self.params
        pos = st["pos"]
        x = layers.embed_tokens(params["embed"], tokens)
        codes = cfg.pattern()
        ai = 0
        new_st = dict(st)
        for code, p in zip(codes, m._per_layer_params(params)):
            h = layers.apply_norm(p["ln1"], x)
            if code in "AW":
                w = cfg.window if code == "W" else 0
                o, kb, vb = mattn.attn_decode_contiguous(
                    p["attn"], h, cfg, st["k_buf"][ai], st["v_buf"][ai],
                    pos, window=w)
                new_st["k_buf"] = new_st["k_buf"].at[ai].set(kb)
                new_st["v_buf"] = new_st["v_buf"].at[ai].set(vb)
                st = new_st
                ai += 1
                x = x + o
            x, _ = m._apply_ffn(p, x)
        new_st["pos"] = pos + 1
        x = layers.apply_norm(params["ln_f"], x)
        return layers.unembed(params["embed"], x, cfg), new_st

    def _sample_and_append(self, reqs: List[Request], logits: jnp.ndarray,
                           first: bool) -> None:
        with TraceAnnotation("engine.sample.guard"):
            logits = jnp.asarray(logits)
            if self.faults is not None and reqs:
                # injected NaN logits: per-row poison, caught by the guard
                bad = [i for i, r in enumerate(reqs)
                       if self.faults.fire("sample", rid=r.rid) == "nan"]
                if bad:
                    logits = logits.at[jnp.asarray(bad)].set(jnp.nan)
            if self.numerics_guard and reqs:
                # per-row isolation: a poisoned row (overflowed
                # activations, injected NaN) fails *its* request;
                # survivors sample as if the bad row never existed (their
                # logits depend only on their own KV pages, so outputs are
                # bit-identical — gated by tests/test_faults.py)
                finite = np.asarray(jnp.all(jnp.isfinite(logits), axis=-1))
                if not finite.all():
                    for r, ok in zip(reqs, finite):
                        if not ok:
                            self.scheduler.fail(r, NumericsError(
                                "non-finite logits in this request's row "
                                f"(step {self.steps})", rid=r.rid,
                                step=self.steps))
                    keep = np.where(finite)[0]
                    reqs = [reqs[i] for i in keep]
                    logits = logits[jnp.asarray(keep)]
        with TraceAnnotation("engine.sample.draw"):
            if not reqs:
                self.rng, _ = jax.random.split(self.rng)  # stream parity
                return
            sp = SampleParams(
                temperature=jnp.asarray([r.temperature for r in reqs],
                                        jnp.float32),
                top_k=jnp.asarray([r.top_k for r in reqs], jnp.int32),
                top_p=jnp.asarray([r.top_p for r in reqs], jnp.float32),
            )
            self.rng, key = jax.random.split(self.rng)
            toks = np.asarray(sample(key, logits, sp))
        with TraceAnnotation("engine.sample.append"):
            now = time.perf_counter()
            for r, t in zip(reqs, toks):
                r.output.append(int(t))
                if first and "ttft_s" not in r.metrics:
                    r.metrics["ttft_s"] = now - r.metrics["t_arrive"]
                    self.stats["first_tokens"] += 1
                    self.stats["prefill_ns"] += round(
                        (now - r.metrics["t_admit"]) * 1e9)

    def _finish_done(self) -> List[Request]:
        with TraceAnnotation("engine.sched.finish"):
            done = []
            for req in list(self.scheduler.running.values()):
                if req.status is not Status.RUNNING:
                    continue  # mid-prefill requests have no fresh sample
                hit_eos = (req.eos_id is not None and req.output
                           and req.output[-1] == req.eos_id)
                if len(req.output) >= req.max_new_tokens or hit_eos:
                    req.metrics["t_done"] = time.perf_counter()
                    req.metrics["tok_s"] = len(req.output) / max(
                        req.metrics["t_done"] - req.metrics["t_arrive"],
                        1e-9)
                    self.scheduler.finish(req)
                    done.append(req)
            return done

    # ------------------------------------------------------------------
    # prefix sharing (paper §III contribution 1: fork + copy-on-write)
    def fork_request(self, src: Request, max_new_tokens: int = 64,
                     **sampling) -> Request:
        """Fork a RUNNING request: the child aliases the parent's *full*
        KV pages (refcount++, zero copies) and gets a fresh copy of the
        partial tail page — the paper's copy-on-write prefix sharing.

        The child enters the batch immediately (no re-prefill of the
        shared prefix) and decodes from the parent's current position.
        """
        if src.status != Status.RUNNING or not self.paged:
            raise InvalidRequest("fork requires a RUNNING request on the "
                                 "paged engine", rid=src.rid)
        if src.total_len + max_new_tokens > self.max_seq_len:
            # the same cap add_request enforces — without it the child's
            # page row outgrows the device table width mid-decode and
            # `_tables_array` (rightly) refuses to truncate it
            raise RequestTooLong("fork child exceeds engine max_seq_len",
                                 rid=src.rid, limit=self.max_seq_len)
        slots = self.scheduler.free_slots()
        if not slots:
            raise PoolExhausted("no free slot for fork", rid=src.rid,
                                resource="slots")
        ps = self.cfg.page_size
        seq = src.prompt + src.output
        # Page math must follow the *cached* length (`mgr.lens`, == the
        # parent's decode position): the last sampled token is not in the
        # pools yet — it is the next decode input.  Sizing by len(seq)
        # skipped the tail copy whenever len(seq) was page-aligned while
        # the cache was still one token short of the boundary, handing the
        # child a never-written tail page.
        cached_len = self.mgr.lens[src.rid]
        full_pages = cached_len // ps
        need_tail = 1 if cached_len % ps else 0
        # available_pages counts detached cached chains (reclaimed on
        # demand inside mgr.reserve), not just the raw free list
        if need_tail + self.scheduler.headroom > self.mgr.available_pages:
            raise PoolExhausted("no pages for fork tail", rid=src.rid,
                                resource="pages")

        child = Request(prompt=list(seq), max_new_tokens=max_new_tokens,
                        parent=src.rid, **sampling)
        child.metrics["t_arrive"] = time.perf_counter()
        # host manager: alias full pages (refcount++), reserve fresh tail.
        # fork is all-or-nothing — on a dry pool it rolls the refcount
        # bumps back and returns False, so a failed fork leaves no
        # half-created child row behind (the headroom check above makes
        # this unreachable in practice, but the engine must not trust it:
        # a False here with the bumps kept would alias live pages).
        if not self.mgr.fork(src.rid, child.rid):
            # replint: disable=allocator-discipline -- fork is all-or-nothing: a False return means its internal rollback already ran
            raise PoolExhausted("no pages for fork tail", rid=src.rid,
                                resource="pages")
        # device: copy the parent's partial tail page into the child's
        if need_tail:
            src_tail = self.mgr.tables[src.rid][full_pages]
            dst_tail = self.mgr.tables[child.rid][full_pages]
            st = self.state
            st["k_pages"] = st["k_pages"].at[:, dst_tail].set(
                st["k_pages"][:, src_tail])
            st["v_pages"] = st["v_pages"].at[:, dst_tail].set(
                st["v_pages"][:, src_tail])
        # enter the running batch at the parent's position
        slot = slots[0]
        child.status = Status.RUNNING
        child.slot = slot
        self.scheduler.running[slot] = child
        src_pos = int(np.asarray(self.state["pos"])[src.slot])
        self.state["pos"] = self.state["pos"].at[slot].set(src_pos)
        for key in ("cross_k", "cross_v"):
            if key in self.state:
                self.state[key] = self.state[key].at[:, slot].set(
                    self.state[key][:, src.slot])
        if "rec" in self.state:
            self.state["rec"] = jax.tree_util.tree_map(
                lambda a: a.at[:, slot].set(a[:, src.slot]),
                self.state["rec"])
        child.metrics["ttft_s"] = 0.0  # prefix shared: no prefill
        return child

    # ------------------------------------------------------------------
    # memory accounting (paper Fig. 1/2 + the <5% overhead objective)
    def memory_report(self) -> Dict[str, float]:
        cfg = self.cfg
        Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim
        n_attn = getattr(self.model, "n_attn_layers", 0)
        item = jnp.dtype(self.dtype).itemsize
        if self.paged:
            # pools are int8 under kv_dtype="int8" (see _init_state) — the
            # accounting must use the *pool* dtype, not the activation
            # dtype, or pool_bytes/reserved_bytes overstate 4× and skew
            # the paper's <5 % overhead metric
            pool_dt = jnp.int8 if cfg.kv_dtype == "int8" else self.dtype
            item = jnp.dtype(pool_dt).itemsize
            cache_bytes = (2 * n_attn * self.num_pages * cfg.page_size
                           * Hkv * hd * item)
            reserved = self.mgr.bytes_reserved(Hkv, hd, n_attn, item)
        else:
            cache_bytes = (2 * n_attn * self.max_slots * self.max_seq_len
                           * Hkv * hd * item)
            reserved = cache_bytes
        live_tokens = sum(r.total_len
                          for r in self.scheduler.running.values())
        minimum = live_tokens * 2 * n_attn * Hkv * hd * item
        pc = self.prefix_cache
        return {
            "pool_bytes": float(cache_bytes),
            "reserved_bytes": float(reserved),
            "theoretical_min_bytes": float(minimum),
            "overhead_frac": (reserved / minimum - 1.0) if minimum else 0.0,
            "used_pages": float(self.mgr.used_pages) if self.paged else -1.0,
            # prefix-cache residency: `cached_pages` are indexed in the
            # radix trie; the `reclaimable` subset is evictable on demand
            # (detached chains), i.e. capacity rather than load
            "cached_pages": float(pc.resident_pages) if pc else 0.0,
            "reclaimable_pages": float(pc.reclaimable()) if pc else 0.0,
            "prefix_hit_tokens": float(pc.hit_tokens) if pc else 0.0,
        }
