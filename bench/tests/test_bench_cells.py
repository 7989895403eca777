"""Every cell's traffic through ``harness.run_cell`` at a tiny size on the
CPU: the result line holds in traced and untraced runs, a broken timed
path reads as not correct, and the fp8 control fails the check that the
engine passes."""

import copy
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import arch  # noqa: E402
import harness  # noqa: E402

CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]
PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
# Limits at this size, between the readings (PERF.md): the engine's bf16
# tokens read 0 below the float32 reference's best logit and its logits
# lie 0.003-0.004 of scale from the reference's; the fp8 control reads
# 0.03 and more over 1000 positions, and 0.037-0.087 of scale.
TINY_LIMIT = 0.01
TINY_DEV = 0.015


def tiny_config(c):
    c = copy.deepcopy(c)
    c["model"] = arch.for_model(c["model"]).tiny(c["model"])
    c["serving"].update(max_slots=2, pool_tokens=1024, page_size=8,
                        prefill_chunk=32, max_seq_len=256, impl="ref")
    c["check"] = {"served_gap": TINY_LIMIT, "logit_dev": TINY_DEV,
                  "tokens_compared": 8}
    return c


def tiny_traffic(t):
    t = copy.deepcopy(t)
    t["prompt_tokens"] = {"min": 8, "max": 40}
    t["output_tokens"] = {"min": 8, "max": 16}
    t["clients"] = 3
    t["greedy_every"] = 2
    if "documents" in t:
        t["documents"].update(count=2, min=24, max=48)
    return t


def run(name, trace, tmp_path, **kw):
    lines = []
    out = harness.run_cell(name, 2**31 + 99, 0.6, trace,
                           config_override=tiny_config,
                           traffic_override=tiny_traffic, peak=PEAK,
                           trace_dir=tmp_path / "trace", log=lines.append,
                           **kw)
    return out, lines


def assert_line(out, lines, cell):
    text = json.dumps(out)
    line = json.loads(text)
    assert list(line)[-1] == "checks"
    for k in ("correct", "attempted", "failed", "metrics", "device"):
        assert k in line
    assert isinstance(line["attempted"], int) and line["attempted"] >= 1
    assert isinstance(line["failed"], int)
    assert 0 <= line["failed"] <= line["attempted"]
    assert isinstance(line["correct"], bool)
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}
    assert lines[-len(line["checks"]):] == [
        f"check {k} {c['value']!r} limit {c['limit']!r}"
        for k, c in line["checks"].items()]
    names = {m["name"] for m in cell.end_to_end + cell.per_layer}
    assert set(line["metrics"]) <= names
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"}
    return line


@pytest.mark.parametrize("trace", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("name", CELLS)
def test_cell_line_holds(name, trace, tmp_path):
    out, lines = run(name, trace, tmp_path)
    cell = harness.load_cell(name)
    line = assert_line(out, lines, cell)
    assert line["correct"], lines
    assert set(line["checks"]) == {"served_gap", "logit_dev",
                                   "tokens_compared", "window_programs"}
    assert line["checks"]["window_programs"]["value"] == 0
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert "decode_batch_mean" in line["metrics"]
    else:
        assert {"setup_s", "output_tok_per_s"} <= set(line["metrics"])


def altered_token_engine():
    """The engine with each greedy request's token changed where it is
    sampled."""
    from repro.serving import Engine

    class Altered(Engine):
        def _sample_and_append(self, reqs, logits, first):
            super()._sample_and_append(reqs, logits, first)
            for r in reqs:
                if r.temperature == 0.0 and r.output:
                    r.output[-1] = (r.output[-1] + 1) % self.cfg.vocab_size
    return Altered


@pytest.mark.parametrize("name", CELLS)
def test_altered_token_is_not_correct(name, tmp_path):
    out, lines = run(name, False, tmp_path,
                     engine_base=altered_token_engine())
    line = assert_line(out, lines, harness.load_cell(name))
    assert line["correct"] is False
    assert line["checks"]["served_gap"]["value"] > TINY_LIMIT


def test_fp8_control_fails_the_check():
    """The reference computed through fp8, put in the engine's place,
    reads above the limit that the engine's bf16 tokens keep."""
    import types

    import numpy as np

    cfg = tiny_config(harness.load_cell(CELLS[0]).config)
    cfg["model"].update(hidden_size=256, head_dim=64, intermediate_size=512,
                        vocab_size=4096)
    model = arch.for_model(cfg["model"])
    w = model.draw(model.shape(cfg["model"]), 5)
    rng = np.random.default_rng(5)
    recs = [types.SimpleNamespace(req=types.SimpleNamespace(
        rid=i, prompt=rng.integers(0, 4096, 64).tolist(),
        output=rng.integers(0, 4096, 250).tolist())) for i in range(4)]
    got = harness.check(model, model.reference(cfg["model"]), w, recs,
                        None, control=True)
    assert got["tokens_compared"] == 1000
    assert got["control_gap"] > TINY_LIMIT
    assert got["control_dev"] > TINY_DEV


@pytest.mark.parametrize("name", CELLS)
def test_control_line_is_not_correct(name, tmp_path):
    """With ``control`` the fp8 reference's numbers stand in the engine's
    place in the checks, and the line reads not correct."""
    out, lines = run(name, False, tmp_path, control=True)
    line = assert_line(out, lines, harness.load_cell(name))
    assert line["correct"] is False
    assert line["checks"]["logit_dev"]["value"] > TINY_DEV


def _tiny_check_inputs(n_reqs=2, n_out=20):
    """Checked requests at a tiny size, with the reference's own logits
    rows as the engine's: ``logit_dev`` reads 0 where every row is there."""
    import types

    import numpy as np

    cfg = tiny_config(harness.load_cell(CELLS[0]).config)
    model = arch.for_model(cfg["model"])
    shape = model.shape(cfg["model"])
    w = model.draw(shape, 3)
    a = model.reference(cfg["model"])
    rng = np.random.default_rng(3)
    recs, rows = [], {}
    for rid in range(n_reqs):
        prompt = rng.integers(0, shape.vocab, 16).tolist()
        out = rng.integers(0, shape.vocab, n_out).tolist()
        x = model.forward(a, w, prompt + out[:-1], len(prompt) - 1)
        lg = np.asarray(model.logits(a, w, x, len(prompt) - 1))
        rows[rid] = list(lg[:n_out])
        recs.append(types.SimpleNamespace(req=types.SimpleNamespace(
            rid=rid, prompt=prompt, output=out)))
    return (model, a), w, recs, rows


@pytest.mark.parametrize("lost", ["no_hook", "row_missing"])
def test_unread_logit_dev_is_not_correct(lost):
    """A configuration that holds ``logit_dev`` fails a run that could not
    read it: no logits kept at all, or a checked request short of a row."""
    ref, w, recs, rows = _tiny_check_inputs()
    limits = {"logit_dev": TINY_DEV}
    checks, passed = harness.held(
        harness.check(*ref, w, recs, rows, control=False), limits)
    assert passed and checks["logit_dev"]["value"] < 1e-6, checks
    if lost == "no_hook":
        rows = None
    else:
        rows[recs[1].req.rid] = rows[recs[1].req.rid][:-1]
    got = harness.check(*ref, w, recs, rows, control=False)
    checks, passed = harness.held(got, limits)
    assert checks["logit_dev"]["value"] is None
    assert passed is False
    assert got["rows_missing"] == (2 if lost == "no_hook" else 1)


def test_serve_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(BENCH / "serve.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
