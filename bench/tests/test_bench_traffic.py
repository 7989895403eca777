"""Traffic generation is a function of the seed, and every seed serves
the same shapes in the same order."""

import json
import sys
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from traffic import Traffic, quantiles, zipf_choices  # noqa: E402

MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json"))


def _load(name):
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _draw(spec, seed, n=40):
    t = Traffic(spec, seed, vocab=49152)
    return t, [t.next() for _ in range(n)]


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    spec = _load(mix)
    _, a = _draw(spec, 2**31 + 12345)
    _, b = _draw(spec, 2**31 + 12345)
    assert [(r.prompt, r.max_new_tokens, r.temperature, r.top_p)
            for r in a] == [(r.prompt, r.max_new_tokens, r.temperature,
                             r.top_p) for r in b]


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_draw_tokens_of_one_sequence_of_shapes(mix):
    spec = _load(mix)
    _, a = _draw(spec, 7)
    _, b = _draw(spec, 8)
    assert [r.prompt for r in a] != [r.prompt for r in b]
    assert [(len(r.prompt), r.max_new_tokens, r.document, r.greedy)
            for r in a] == [(len(r.prompt), r.max_new_tokens, r.document,
                             r.greedy) for r in b]


@pytest.mark.parametrize("mix", MIXES)
def test_requests_keep_to_the_mix(mix):
    spec = _load(mix)
    t, reqs = _draw(spec, 3, n=300)
    p, o = spec["prompt_tokens"], spec["output_tokens"]
    doc_lens = [len(d) for d in t.docs]
    for r in reqs:
        q = len(r.prompt) - (doc_lens[r.document] if r.document >= 0 else 0)
        assert p["min"] <= q <= p["max"]
        assert o["min"] <= r.max_new_tokens <= o["max"]
        assert max(r.prompt) < 49152
    greedy = [r.index for r in reqs if r.greedy]
    assert greedy == list(range(0, 300, spec["greedy_every"]))
    assert t.max_total_tokens() <= 4096


def test_lognormal_quantiles_have_the_stated_median():
    q = quantiles({"median": 1024, "sigma": 0.5, "min": 256, "max": 3072},
                  255)
    assert q.min() >= 256 and q.max() <= 3072
    assert abs(int(np.median(q)) - 1024) <= 1


def test_zipf_ranks_fall_with_popularity():
    c = Counter(zipf_choices(8, 1.1, 256).tolist())
    counts = [c[i] for i in range(8)]
    assert counts == sorted(counts, reverse=True) and counts[0] > counts[-1]


def test_open_loop_arrivals_are_fixed_and_bursty():
    spec = {"loop": "open", "rate_per_s": 5.0,
            "burst": {"period_s": 2.0, "size": 4},
            "prompt_tokens": {"min": 8, "max": 16},
            "output_tokens": {"min": 2, "max": 4}, "temperature": 0.7}
    a = Traffic(spec, 11, vocab=100).arrivals(10.0)
    b = Traffic(spec, 12, vocab=100).arrivals(10.0)
    assert np.array_equal(a, b)
    assert np.all(np.diff(a) >= 0) and a[-1] < 10.0
    assert np.sum(a == 2.0) == 4
