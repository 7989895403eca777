"""One run of one cell: set-up, a measured window, drain, check, report.

``run_cell`` is everything beneath the command line: it builds the cell's
engine on whatever JAX platform it finds, so the CPU tests drive it at a
tiny size while ``serve.py`` refuses to run anywhere but on a TPU.

Clients and timing (all on the host's clock, ``time.perf_counter``):

- set-up draws the weights and builds the engine through the
  configuration's architecture module (``bench/arch/``), prefills the
  shared documents of the mix (if any), compiles the per-row programs for
  every row count, then runs the mix's own traffic until every slot has
  been busy and a request has finished; so the traffic is in steady state
  when the window opens;
- in the window clients keep sending; ``attempted`` counts the requests
  sent in it, ``failed`` those of them that were refused, ended FAILED,
  or had no first token when the drain gave up;
- a token counts for the window when the host saw it inside the window,
  whichever request it belongs to; a gap between two tokens of a request
  counts when its later token does;
- after the window no request is sent; the engine steps on until every
  request sent in the window has its first token.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import arch
import devtrace
import reference
import weights
from repro.serving import Engine, Request, Status
from repro.serving.sampler import SampleParams, sample
from traffic import RequestSpec, Traffic

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
SPANNED = ("_prefill_chunk_step", "_prefill", "_decode",
           "_sample_and_append")
WARM_THREADS = 8  # row counts compiled side by side in set-up
ARRIVALS_S = 3600.0  # an open loop's schedule is drawn this far ahead


@dataclass
class Cell:
    name: str
    workload: Dict
    config: Dict
    traffic: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]


def _for_cell(metrics: List[Dict], name: str) -> List[Dict]:
    return [m for m in metrics if name in m.get("workloads", [name])]


def load_cell(name: str) -> Cell:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    wl = {w["name"]: w for w in bench["workloads"]}.get(name)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg = {c["name"]: c for c in bench["configs"]}[wl["config"]]
    config = json.loads((ROOT / cfg["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{wl['traffic']}.json").read_text())
    e2e = _for_cell(bench["end_to_end"], name)
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in _for_cell(bench["per_layer"], name)
                 if m["moves"] in moved]
    return Cell(name, wl, config, traffic, e2e, per_layer)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------
def spanned_engine_class(base=Engine):
    """The engine with a profiler span around each of its phases that
    exists (``SPANNED``), named ``engine.<phase>``.  The sampling phase
    also keeps a reference to the logits it is handed whenever a greedy
    request is among its rows (``bench_logits``: no copy, no transfer),
    so the check can hold the logits the timed path produced against the
    reference."""

    def wrap(name):
        inner = getattr(base, name)
        label = "engine." + name.strip("_")

        def method(self, *args, **kw):
            with jax.profiler.TraceAnnotation(label):
                if name == "_sample_and_append":
                    self.keep_logits(*args, **kw)
                return inner(self, *args, **kw)
        return method

    def keep_logits(self, reqs=None, logits=None, *rest, **kw):
        if (self.bench_logits is not None and isinstance(reqs, list)
                and getattr(logits, "ndim", 0) == 2
                and logits.shape[0] == len(reqs)
                and any(getattr(r, "temperature", 1.0) == 0.0
                        for r in reqs)):
            self.bench_logits.append((logits, [r.rid for r in reqs]))

    attrs = {n: wrap(n) for n in SPANNED if hasattr(base, n)}
    attrs["keep_logits"] = keep_logits
    attrs["bench_logits"] = None
    return type("SpannedEngine", (base,), attrs)


def logits_rows(eng, rids) -> Dict[int, List[np.ndarray]]:
    """The logits rows the engine sampled each of ``rids``' tokens from,
    in order, as float32 numpy (each kept array is copied once)."""
    out: Dict[int, List[np.ndarray]] = {r: [] for r in rids}
    for arr, row_rids in eng.bench_logits or []:
        if not out.keys() & set(row_rids):
            continue
        host = np.asarray(arr).astype(np.float32)
        for i, rid in enumerate(row_rids):
            if rid in out:
                out[rid].append(host[i])
    return out


# ---------------------------------------------------------------------------
# records
# ---------------------------------------------------------------------------
@dataclass
class Rec:
    """One request as its client saw it."""

    spec: RequestSpec
    req: object
    t_send: float
    times: List[float] = field(default_factory=list)  # per output token
    refused: bool = False


@dataclass
class StepRec:
    t0: float
    t1: float
    chunk_rows: List[Tuple[int, int]]  # (cached tokens, new prompt tokens)
    decode_ctx: List[int]  # context length of each decoded row
    tokens: int  # output tokens the host saw after this step
    admitted_tokens: int  # prompt tokens of requests admitted this step

    @property
    def wall(self) -> float:
        return self.t1 - self.t0


class Clients:
    """The traffic's client population in front of one engine."""

    def __init__(self, eng, traffic: Traffic):
        self.eng = eng
        self.traffic = traffic
        self.recs: List[Rec] = []
        self.live: List[Rec] = []
        self.steps: List[StepRec] = []
        self.slots: List[Optional[Rec]] = [None] * max(traffic.clients, 0)
        self.due: Optional[np.ndarray] = None
        self.t_open = 0.0

    def send_one(self, now: float) -> Rec:
        s = self.traffic.next()
        req = Request(prompt=list(s.prompt),
                      max_new_tokens=s.max_new_tokens,
                      temperature=s.temperature, top_p=s.top_p)
        rec = Rec(s, req, now)
        try:
            self.eng.add_request(req)
        except Exception:  # refused at the door: counts as failed
            rec.refused = True
        self.recs.append(rec)
        if not rec.refused:
            self.live.append(rec)
        return rec

    def send(self) -> None:
        """Closed loop: every idle client sends; open loop: every request
        now due is sent (timed from when it was due)."""
        now = time.perf_counter()
        if self.traffic.loop == "closed":
            for i, rec in enumerate(self.slots):
                if rec is None or rec.refused or rec.req.done:
                    self.slots[i] = self.send_one(now)
            return
        if self.due is None:
            self.t_open = now
            self.due = self.traffic.arrivals(ARRIVALS_S)
        while len(self.due) and self.t_open + self.due[0] <= now:
            self.send_one(self.t_open + float(self.due[0]))
            self.due = self.due[1:]

    def step(self) -> StepRec:
        before = [(r, r.req.status, r.req.prefill_pos, len(r.req.output))
                  for r in self.live]
        t0 = time.perf_counter()
        self.eng.step()
        t1 = time.perf_counter()
        chunk_rows, decode_ctx = [], []
        tokens = admitted = 0
        waiting = (Status.WAITING, Status.PREEMPTED)
        for rec, status, pos, n_out in before:
            req = rec.req
            if status in waiting and req.status not in waiting:
                admitted += req.total_len - len(req.output)
            start = req.cached_prefix if status in waiting else pos
            if status is not Status.RUNNING and req.prefill_pos > start:
                chunk_rows.append((start, req.prefill_pos - start))
            new = len(req.output) - n_out
            if new > 0:
                tokens += new
                rec.times.extend([t1] * new)
                if status is Status.RUNNING:
                    decode_ctx.append(req.total_len - 1)
        self.live = [r for r in self.live if not r.req.done]
        out = StepRec(t0, t1, chunk_rows, decode_ctx, tokens, admitted)
        self.steps.append(out)
        return out


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
def prefill_documents(eng, traffic: Traffic) -> None:
    """Serve each shared document once, so the prefix cache holds it."""
    reqs = [Request(prompt=list(d), max_new_tokens=1) for d in traffic.docs]
    if reqs:
        eng.generate(reqs)


def warm_row_shapes(eng, vocab: int, dtype) -> None:
    """Compile, once for every row count a step can carry, the engine's
    eager per-row host programs: the gather of live rows' logits, the
    finiteness guard, the sampler, and the scatter of positions into
    their slots.  Each row count is its own set of programs, so they
    compile side by side in a few threads; none is then first met inside
    the window."""
    B = eng.max_slots
    logits = jnp.zeros((B, vocab), dtype)
    key = jax.random.PRNGKey(0)

    def rows(n):
        idx = np.arange(n)
        lg = jnp.asarray(logits)[idx]
        np.asarray(jnp.all(jnp.isfinite(lg), axis=-1))
        sp = SampleParams(
            temperature=jnp.asarray([0.7] * n, jnp.float32),
            top_k=jnp.asarray([0] * n, jnp.int32),
            top_p=jnp.asarray([0.9] * n, jnp.float32))
        np.asarray(sample(key, lg, sp))
        jax.block_until_ready(eng.state["pos"].at[
            jnp.asarray(idx.tolist())].set(jnp.asarray(np.zeros(n, np.int32))))

    with ThreadPoolExecutor(WARM_THREADS) as ex:
        for f in [ex.submit(rows, n) for n in range(1, B + 1)]:
            f.result()


def warm_up(clients: Clients, max_s: float) -> None:
    """Run the cell's own traffic until every slot has been busy at once
    and some request has finished (or ``max_s`` has passed)."""
    eng = clients.eng
    full = finished = False
    t_end = time.perf_counter() + max_s
    while not (full and finished) and time.perf_counter() < t_end:
        clients.send()
        clients.step()
        full = full or len(eng.scheduler.running) == eng.max_slots
        finished = finished or any(r.req.done for r in clients.recs)


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache while
    it is entered, and the seconds that took."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.loaded = 0
        self.compiled = 0
        self.seconds = 0.0

    def _dur(self, event, duration, **kw):
        if event == self.EVENT:
            self.loaded += 1
            self.seconds += duration

    def _ev(self, event, **kw):
        if event == "/jax/compilation_cache/cache_misses":
            self.compiled += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._dur)
        jax.monitoring.unregister_event_listener(self._ev)


# ---------------------------------------------------------------------------
# the check against the reference
# ---------------------------------------------------------------------------
def pick_checked(recs: List[Rec], n: int, seed: int,
                 since: float) -> List[Rec]:
    """Greedy requests finished after ``since``: the longest, and ``n - 1``
    more drawn from the seed."""
    done = [r for r in recs if r.spec.greedy and r.times
            and r.req.status is Status.FINISHED and r.times[-1] >= since]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.req.total_len, -r.spec.index))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 1])
    k = min(n - 1, len(rest))
    chosen = rng.choice(len(rest), size=k, replace=False) if k else []
    return [longest] + [rest[i] for i in sorted(chosen)]


def check(model, a, w: Dict, checked: List[Rec],
          served_logits: Optional[Dict[int, List[np.ndarray]]],
          control: bool) -> Dict:
    """Over every output token of the checked requests: the widest gap
    below the reference's best logit of a served token (``served_gap``),
    and the widest |logit difference| between the logits the engine
    sampled from and the reference's, as a share of the largest reference
    logit at that position (``logit_dev``).  ``logit_dev`` is ``None``,
    which no limit holds, when the engine's logits were not kept or a
    checked request lacks a row for any of its tokens.  With ``control``,
    the same two numbers for the fp8 reference put in the engine's place
    (``control_gap``, ``control_dev``).  ``model`` is the architecture
    module (``arch.for_model``), ``a`` its ``reference`` sizes and ``w``
    the weights drawn."""
    got = {"served_gap": 0.0, "logit_dev": 0.0, "tokens_compared": 0,
           "rows_missing": 0}
    if control:
        got.update(control_gap=0.0, control_dev=0.0)
    for rec in checked:
        prompt, out = rec.req.prompt, list(rec.req.output)
        n, first = len(out), len(prompt) - 1
        x = model.forward(a, w, list(prompt) + out[:-1], first)
        ref = model.logits(a, w, x, first)
        prog = np.zeros(ref.shape, np.float32)
        rows = (served_logits or {}).get(rec.req.rid, [])
        if len(rows) == n:
            prog[:n] = np.stack(rows)
        else:
            got["rows_missing"] += 1
        r = reference.compare(ref, np.asarray(out), prog, n)
        got["served_gap"] = max(got["served_gap"], r["gap"])
        if len(rows) == n:
            got["logit_dev"] = max(got["logit_dev"], r["dev"])
        got["tokens_compared"] += n
        if control:
            q = model.logits(a, w, model.forward(
                a, w, list(prompt) + out[:-1], first, quant=True),
                first, quant=True)
            top = np.asarray(jnp.argmax(q, axis=-1))[:n]
            c = reference.compare(ref, top, q, n)
            got["control_gap"] = max(got["control_gap"], c["gap"])
            got["control_dev"] = max(got["control_dev"], c["dev"])
            del q
        del x, ref
    if served_logits is None or got["rows_missing"]:
        got["logit_dev"] = None
    return got


def held(got: Dict, limits: Dict) -> Tuple[Dict, bool]:
    """Each number the configuration holds, beside its limit, and whether
    all keep it.  ``tokens_compared`` is a floor, the rest are ceilings;
    a number the run could not read (``None`` or absent) fails."""
    checks, ok = {}, True
    for k, limit in limits.items():
        v = got.get(k)
        checks[k] = {"value": v, "limit": limit}
        if v is None:
            ok = False
        else:
            ok = ok and (v >= limit if k == "tokens_compared"
                         else v <= limit)
    return checks, ok


def as_control(got: Dict) -> Dict:
    """The check's numbers with the fp8 control's in the engine's place."""
    return dict(got, served_gap=got["control_gap"],
                logit_dev=got["control_dev"])


# ---------------------------------------------------------------------------
# end-to-end numbers
# ---------------------------------------------------------------------------
def end_to_end(clients: Clients, w0: float, w1: float) -> Dict[str, float]:
    sent = [r for r in clients.recs if w0 <= r.t_send < w1]
    ttft = [(r.times[0] - r.t_send) * 1e3 for r in sent if r.times]
    gaps, tokens = [], 0
    for r in clients.recs:
        t = np.asarray(r.times)
        tokens += int(np.sum((t >= w0) & (t <= w1)))
        if len(t) > 1:
            later = t[1:]
            keep = (later >= w0) & (later <= w1)
            gaps.extend(((later - t[:-1])[keep] * 1e3).tolist())
    out = {"output_tok_per_s": tokens / (w1 - w0)}
    if ttft:
        out["ttft_p50_ms"] = float(np.percentile(ttft, 50))
        out["ttft_p90_ms"] = float(np.percentile(ttft, 90))
    if gaps:
        out["itl_p50_ms"] = float(np.percentile(gaps, 50))
        out["itl_p99_ms"] = float(np.percentile(gaps, 99))
    out["_samples"] = {"requests": len(ttft), "gaps": len(gaps),
                       "tokens": tokens}
    return out


@dataclass
class RunView:
    """What a per-layer reducer reads."""

    steps: List[StepRec]  # steps of the window
    w0: float
    w1: float
    shape: arch.Shape
    peak: Dict
    counters: Dict[str, Dict[str, int]]  # "before"/"after" the window
    trace: Optional[devtrace.Trace]  # the traced window, or None


def reducer(name: str) -> Callable[[RunView], Optional[float]]:
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.reduce


def load_peak(kind: str) -> Dict:
    table = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: Optional[float] = None,
             config_override: Optional[Callable[[Dict], Dict]] = None,
             traffic_override: Optional[Callable[[Dict], Dict]] = None,
             engine_base=Engine, control: bool = False,
             peak: Optional[Dict] = None, trace_dir: Optional[Path] = None,
             log=print) -> Dict:
    """One run of cell ``name``; returns the result line as a dict (its
    ``checks`` key last).  Besides the configuration's own limits, the
    checks hold ``window_programs``, the programs compiled or loaded
    inside the window, at 0.  With ``control`` the fp8 control's numbers
    are held in place of the engine's, so the line reads not correct."""
    t_start = time.perf_counter() if t_start is None else t_start
    cell = load_cell(name)
    config = cell.config
    if config_override is not None:
        config = config_override(config)
    tspec = cell.traffic
    if traffic_override is not None:
        tspec = traffic_override(tspec)
    serving = config["serving"]
    dev = jax.devices()[0]
    peak = peak or load_peak(dev.device_kind)
    model = arch.for_model(config["model"])
    shape = model.shape(config["model"])
    ref_arch = model.reference(config["model"])
    dtype = jnp.dtype(config["model"]["torch_dtype"])

    def phase(label, fn, *args):
        t = time.perf_counter()
        with CompileCounter() as c:
            out = fn(*args)
            jax.block_until_ready(out)
        log(f"setup {label}: {time.perf_counter() - t:.3f} s, "
            f"{c.loaded} programs compiled or loaded ({c.compiled} "
            f"compiled) in {c.seconds:.3f} s")
        return out

    w = phase("weights", model.draw, shape, seed, dtype)
    cls = spanned_engine_class(engine_base)
    eng = phase("engine", lambda: cls(
        model.engine_config(config), model.to_engine(w, shape),
        max_slots=serving["max_slots"], max_seq_len=serving["max_seq_len"],
        pool_tokens=serving["pool_tokens"], impl=serving["impl"],
        rng=weights.seed_key(seed + 1), dtype=dtype,
        prefill_chunk=serving["prefill_chunk"],
        prefix_cache=bool(tspec.get("prefix_cache", False))))
    traffic = Traffic(tspec, seed, shape.vocab)
    if traffic.max_total_tokens() > serving["max_seq_len"]:
        raise ValueError("the traffic's longest request exceeds max_seq_len")
    phase("documents", prefill_documents, eng, traffic)
    phase("row shapes", warm_row_shapes, eng, shape.vocab, dtype)
    eng.bench_logits = []
    clients = Clients(eng, traffic)
    phase("warm-up traffic", warm_up, clients,
          float(tspec.get("warmup_max_s", 600)))
    n_warm = len(clients.steps)

    counters = {"before": eng.robustness_report()}
    tdir = trace_dir or (ROOT / ".bench_trace" / name)
    if trace:
        devtrace.start(tdir)
    with CompileCounter() as cc, jax.profiler.TraceAnnotation("bench.window"):
        w0 = time.perf_counter()
        setup_s = w0 - t_start
        deadline = w0 + seconds
        while time.perf_counter() < deadline:
            with jax.profiler.TraceAnnotation("bench.clients"):
                clients.send()
            with jax.profiler.TraceAnnotation("bench.step"):
                clients.step()
        w1 = clients.steps[-1].t1
    counters["after"] = eng.robustness_report()
    if trace:
        t = time.perf_counter()
        devtrace.stop()
        log(f"trace written in {time.perf_counter() - t:.3f} s")
    window_steps = clients.steps[n_warm:]

    sent = [r for r in clients.recs if w0 <= r.t_send < w1]
    drain_end = time.perf_counter() + float(tspec.get("drain_s", 120))
    while (any(not r.times and not r.req.done and not r.refused
               for r in sent) and time.perf_counter() < drain_end):
        clients.step()
    failed = sum(1 for r in sent if r.refused or not r.times
                 or r.req.status is Status.FAILED)

    stats = dev.memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))
    e2e = end_to_end(clients, w0, w1)
    view = RunView(window_steps, w0, w1, shape, peak, counters, None)
    checked = pick_checked(clients.recs, int(tspec.get("check_requests", 4)),
                           seed, w0)
    served_logits = None
    if hasattr(cls, "_sample_and_append"):
        served_logits = logits_rows(eng, [r.req.rid for r in checked])
    del eng, clients.eng
    gc.collect()

    t = time.perf_counter()
    got = check(model, ref_arch, w, checked, served_logits, control)
    log(f"reference check: {time.perf_counter() - t:.3f} s")
    del w
    if control:
        log(f"engine served_gap {got['served_gap']!r} "
            f"logit_dev {got['logit_dev']!r}; the control's numbers are "
            f"held in their place")
        got = as_control(got)
    got["window_programs"] = cc.loaded
    checks, correct = held(got, dict(config["check"], window_programs=0))

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    result: Dict = {"correct": bool(correct), "attempted": len(sent),
                    "failed": int(failed)}
    metrics: Dict[str, Dict] = {}
    breakdown = None
    if trace:
        t = time.perf_counter()
        view.trace = devtrace.load(tdir)
        log(f"trace read in {time.perf_counter() - t:.3f} s")
        busy, window = view.trace.busy_window()
        device.update(busy_s=busy, window_s=window)
        breakdown = view.trace.breakdown()
        for m in cell.per_layer:
            v = reducer(m["name"])(view)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e["setup_s"] = setup_s
        for m in cell.end_to_end:
            if m["name"] in e2e:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if breakdown is not None:
        result["breakdown"] = breakdown
    log(f"window {w1 - w0:.3f} s, {len(window_steps)} steps, "
        f"{e2e['_samples']['requests']} first tokens, "
        f"{e2e['_samples']['gaps']} gaps, {e2e['_samples']['tokens']} "
        f"tokens; ttft_p90_ms {e2e.get('ttft_p90_ms')}; "
        f"programs loaded in the window {cc.loaded}, compiled {cc.compiled}")
    log(f"setup_s {setup_s:.3f}; checked {len(checked)} requests, "
        f"{got['rows_missing']} without their logits rows")
    for k, c in checks.items():
        log(f"check {k} {c['value']!r} limit {c['limit']!r}")
    result["checks"] = checks
    return result
