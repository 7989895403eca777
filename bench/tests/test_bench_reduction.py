"""The reduction from a trace and the step records to the per-layer
metrics: on hand-made intervals with known answers, on a slice of a trace
recorded on a TPU v5e, and with what a reducer reads taken away."""

import gzip
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import arch  # noqa: E402
import devtrace  # noqa: E402
import harness  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MOE = json.loads((BENCH / "configs" / "granite-moe-1b-a400m.json")
                 .read_text())["model"]
SHAPE = arch.for_model(MOE).shape(MOE)
METRICS = [m["name"] for m in json.loads(
    (BENCH.parent / "BENCHMARK.json").read_text())["per_layer"]]


def synthetic():
    """Window 0..100 ns on one device: programs run 10-30 and 50-60;
    the decode kernel 12-20, the prefill kernel 52-56; the host is in
    ``engine.sample_and_append`` over 30-50 and in a bare step over
    60-100."""
    d = "/device:TPU:0"
    return devtrace.Trace(
        modules={d: [("jit__decode_fn", 10, 30), ("jit_paged_prefill", 50, 60),
                     ("jit_other", 12, 14)]},
        ops={d: [("jit__decode_fn/paged_attention", 12, 20),
                 ("jit__decode_fn/fusion", 20, 30),
                 ("jit_paged_prefill/paged_prefill", 52, 56)]},
        spans=[("bench.window", 0, 100), ("bench.step", 1, 100),
               ("engine.sample_and_append", 30, 50)],
        window=(0, 100))


def test_busy_is_the_union_of_programs():
    busy, window = synthetic().busy_window()
    assert busy == pytest.approx(30e-9) and window == pytest.approx(100e-9)


def test_idle_gaps_are_named_by_the_innermost_span():
    gaps = synthetic().idle_gaps()
    assert gaps[0] == ["bench.step", pytest.approx(40e-9)]
    assert ["engine.sample_and_append", pytest.approx(20e-9)] in gaps
    assert len(gaps) == 3


def test_kernel_time_matches_instruction_names():
    t = synthetic()
    assert t.op_seconds(devtrace.kernel_match(("paged_attention",))) == \
        pytest.approx(8e-9)
    assert t.op_seconds(devtrace.kernel_match(("no_such_kernel",))) is None
    assert t.top_ops()[0] == ["jit__decode_fn/fusion", pytest.approx(10e-9)]


def test_instruction_names():
    assert devtrace.instruction(
        "%paged_attention.24 = (f32[16,8]{1,0}) custom-call(...)") == \
        "paged_attention"
    assert devtrace.instruction("%copy.106 = bf16[512] copy(x)") == "copy"
    assert devtrace.program_name("jit__decode_fn(4248164808701085741)") == \
        "jit__decode_fn"


def steps():
    S = harness.StepRec
    return [S(0.0, 1.0, [(0, 512)], [100, 200], 2, 512),
            S(1.0, 1.1, [], [101, 201, 50], 3, 0),
            S(1.1, 1.2, [], [], 0, 0)]


def view(trace=None, counters=None):
    return harness.RunView(
        steps(), 0.0, 1.2, SHAPE, PEAK,
        counters or {"before": {"prefix_hit_tokens": 10},
                     "after": {"prefix_hit_tokens": 266}}, trace)


def test_step_metrics():
    v = view()
    r = {m: harness.reducer(m)(v) for m in METRICS}
    assert r["chunk_step_ms"] == pytest.approx(1000.0)
    assert r["decode_batch_mean"] == pytest.approx(2.5)
    assert r["prefix_hit_share"] == pytest.approx(50.0)
    want = sum(SHAPE.decode_flops(c) for c in (101, 201, 50))
    assert r["mfu.decode"] == pytest.approx(
        100 * want / (0.1 * PEAK["bf16_flops_per_s"]))
    assert 0 < r["mfu.prefill"] < 100 and 0 < r["mfu"] < 100


def test_reducers_return_none_when_what_they_read_is_gone():
    v = view(counters={"before": {}, "after": {}})
    for m in ("decode_attn_roofline", "prefill_attn_roofline",
              "device_idle_share", "prefix_hit_share"):
        assert harness.reducer(m)(v) is None
    t = synthetic()
    t.ops = {d: [] for d in t.ops}
    v = view(trace=t)
    assert harness.reducer("decode_attn_roofline")(v) is None
    assert harness.reducer("device_idle_share")(v) == pytest.approx(70.0)


@pytest.mark.parametrize("path", sorted(DATA.glob("*_slice.json.gz")),
                         ids=lambda p: p.name.split("_")[0])
def test_recorded_trace_slice(path):
    """A 0.4-s slice of a window recorded on a TPU v5e (bench/serve.py
    --trace 1), reduced as the benchmark reduces it."""
    with gzip.open(path, "rt") as f:
        t = devtrace.from_json(json.load(f))
    want = json.loads(path.with_name(
        path.name.replace("_slice.json.gz", "_expect.json")).read_text())
    busy, window = t.busy_window()
    assert 0 < busy <= window
    assert [busy, window] == pytest.approx(want["busy_window"])
    b = t.breakdown()
    assert b["device_ops"] == want["breakdown"]["device_ops"]
    assert b["idle_gaps"] == want["breakdown"]["idle_gaps"]
    k = t.op_seconds(devtrace.kernel_match(("paged_attention",)))
    assert k == pytest.approx(want["paged_attention_s"])
