"""The engine's own spans and counters, and the per-layer metrics that read
them: a tiny engine traced under ``jax.profiler`` and read back through
``devtrace.load``, and the four reducers on hand-made spans and counters
with known answers and with their input taken away."""

import json
import sys
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import arch  # noqa: E402
import devtrace  # noqa: E402
import harness  # noqa: E402

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MOE = json.loads((BENCH / "configs" / "granite-moe-1b-a400m.json")
                 .read_text())["model"]
SHAPE = arch.for_model(MOE).shape(MOE)
ENGINE_SPANS = [
    "engine.step", "engine.sched.admit", "engine.sched.extend",
    "engine.sched.finish", "engine.tables", "engine.prefill.prep",
    "engine.prefill.model", "engine.prefill.merge", "engine.prefix.insert",
    "engine.decode.prep", "engine.decode.launch", "engine.decode.merge",
    "engine.sample.guard", "engine.sample.draw", "engine.sample.append"]
NEW = ["queue_wait_ms", "admit_to_first_ms", "decode_step_idle_ms",
       "chunk_step_idle_ms"]


def test_new_metrics_are_declared_for_both_cells():
    """Each of them is read in every cell, a cell added later too: it
    lists no cells of its own."""
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert "workloads" not in declared[name]
    for cell in (w["name"] for w in bench["workloads"]):
        read = {m["name"] for m in harness.load_cell(cell).per_layer}
        assert set(NEW) <= read, cell


@pytest.mark.parametrize("chunk", [None, 16], ids=["monolithic", "chunked"])
def test_engine_spans_nest_in_steps(chunk, tmp_path):
    """Every phase span fires in a served run with the prefix cache on,
    and each lies inside an ``engine.step`` span."""
    from repro.configs import get_smoke
    from repro.serving import Engine, Request
    eng = Engine(get_smoke("llama2-7b"), max_slots=2, max_seq_len=64,
                 prefill_chunk=chunk, prefix_cache=True)
    head = list(range(1, 17))
    reqs = [Request(prompt=head + [20 + i] * (4 + i), max_new_tokens=3)
            for i in range(3)]
    devtrace.start(tmp_path)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            eng.generate(reqs)
    finally:
        devtrace.stop()
    t = devtrace.load(tmp_path)
    names = {n for n, _, _ in t.spans}
    assert set(ENGINE_SPANS) <= names
    steps = [(s, e) for n, s, e in t.spans if n == "engine.step"]
    assert len(steps) == eng.steps
    for n, s, e in t.spans:
        if n.startswith("engine.") and n != "engine.step":
            assert any(s0 <= s and e <= e0 for s0, e0 in steps), n


def trace():
    """Window 0..1000 ns on one device.  Decode-only steps 100-300 (busy
    150-250: idle 100) and 300-400 (busy 300-320, 390-410: idle 70); a
    chunk step 500-900 with a decode launch too (busy 600-700: idle 300);
    an admission-only step 950-990; and a step span that starts before the
    window, left out."""
    d = "/device:TPU:0"
    spans = [("bench.window", 0, 1000),
             ("engine.step", -50, 50), ("engine.decode.launch", -40, -30),
             ("engine.step", 100, 300), ("engine.decode.launch", 110, 140),
             ("engine.step", 300, 400), ("engine.decode.launch", 305, 310),
             ("engine.step", 500, 900), ("engine.prefill.model", 510, 590),
             ("engine.decode.launch", 700, 720),
             ("engine.step", 950, 990), ("engine.sched.admit", 955, 960)]
    return devtrace.Trace(
        modules={d: [("jit__decode_fn", 150, 250), ("p", 300, 320),
                     ("p", 390, 410), ("jit_paged_prefill", 600, 700)]},
        ops={d: []}, spans=spans, window=(0, 1000))


def counters():
    return {"before": {"admitted": 10, "queue_wait_ns": 4_000_000_000,
                       "first_tokens": 9, "prefill_ns": 1_000_000_000},
            "after": {"admitted": 14, "queue_wait_ns": 24_000_000_000,
                      "first_tokens": 12, "prefill_ns": 10_000_000_000}}


def view(t=None, c=None):
    return harness.RunView([], 0.0, 1.0, SHAPE, PEAK,
                           c if c is not None else counters(), t)


def test_reducers_known_answers():
    v = view(trace())
    r = {m: harness.reducer(m)(v) for m in NEW}
    assert r["queue_wait_ms"] == pytest.approx(5000.0)
    assert r["admit_to_first_ms"] == pytest.approx(3000.0)
    assert r["decode_step_idle_ms"] == pytest.approx(85e-6)
    assert r["chunk_step_idle_ms"] == pytest.approx(300e-6)


def _without(t, name):
    t.spans = [sp for sp in t.spans if sp[0] != name]
    return t


@pytest.mark.parametrize("gone", [
    "no trace", "no device", "no engine.step", "no engine.prefill.model",
    "no engine.decode.launch"])
def test_idle_reducers_none_when_input_is_gone(gone):
    t = trace()
    if gone == "no trace":
        t = None
    elif gone == "no device":
        t.modules = {}
    else:
        t = _without(t, gone.split()[1])
    r = {m: harness.reducer(m)(view(t))
         for m in ("decode_step_idle_ms", "chunk_step_idle_ms")}
    if gone in ("no trace", "no device", "no engine.step"):
        assert r == {"decode_step_idle_ms": None,
                     "chunk_step_idle_ms": None}
    elif gone == "no engine.prefill.model":
        # the chunk step now reads as a decode-only step
        assert r["chunk_step_idle_ms"] is None
        assert r["decode_step_idle_ms"] == pytest.approx(470e-6 / 3)
    else:
        assert r["decode_step_idle_ms"] is None
        assert r["chunk_step_idle_ms"] == pytest.approx(300e-6)


@pytest.mark.parametrize("gone", ["before", "after", "none_admitted"])
def test_counter_reducers_none_when_input_is_gone(gone):
    c = counters()
    if gone == "none_admitted":
        c["after"].update(admitted=10, first_tokens=9)
    else:
        c[gone] = {}
    for m in ("queue_wait_ms", "admit_to_first_ms"):
        assert harness.reducer(m)(view(None, c)) is None
