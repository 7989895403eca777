"""Traffic generation: one generic client population driven by a data file.

A traffic mix is a JSON file under ``bench/traffic/``.  Its keys:

- ``loop``: ``"closed"`` (``clients`` users, each sends its next request
  when its last one ends, no think time) or ``"open"`` (requests due on a
  schedule: Poisson arrivals at ``rate_per_s``, plus ``burst.size``
  requests every ``burst.period_s`` seconds when ``burst`` is given).
- ``prompt_tokens`` / ``output_tokens``: ``{"median", "sigma", "min",
  "max"}`` for a lognormal clipped to ``[min, max]``, or ``{"min", "max"}``
  alone for a uniform spread.
- ``documents`` (optional): ``{"count", "min", "max", "zipf_s"}``.  The
  documents are prefilled during set-up; each request then asks about one
  of them (chosen by Zipf over their popularity rank) and its prompt is
  the document followed by ``prompt_tokens`` fresh question tokens.
- ``temperature`` / ``top_p``: sampling of the ordinary requests;
  ``greedy_every``: every n-th request is greedy (temperature 0), so that
  its tokens can be checked against the reference.
- ``size_pool``: how many request shapes the fixed order holds before it
  repeats.

Every seed serves the same shapes in the same order: the prompt, output
and document choices are the quantiles of their distributions, laid out
in one fixed shuffled order, and the seed draws only the token ids (and,
in the harness, the weights).  The engine's schedule is a function of
the shapes alone (no request stops early), so two seeds do the same work
step for step, and the spread between runs is the system's own.
"""

from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List, Optional

import numpy as np

ORDER_SEED = 20250609  # the one order of shapes that every run serves


@dataclass
class RequestSpec:
    """One request as a client sends it."""

    index: int  # order of sending, from 0
    prompt: List[int]
    max_new_tokens: int
    temperature: float
    top_p: float
    document: int  # index of the shared document, -1 when none

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


def quantiles(dist: Dict, n: int) -> np.ndarray:
    """``n`` token counts at the mid-quantiles of ``dist``, clipped and
    rounded: the same multiset for every seed."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if "median" in dist:
        z = np.asarray([NormalDist().inv_cdf(x) for x in u])
        vals = np.exp(np.log(dist["median"]) + dist["sigma"] * z)
    else:
        vals = lo + u * (hi - lo)
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def zipf_choices(count: int, s: float, n: int) -> np.ndarray:
    """``n`` document ranks whose frequencies follow Zipf(``s``) over
    ``count`` documents, laid out by quantile (no sampling noise)."""
    w = 1.0 / np.arange(1, count + 1) ** s
    cdf = np.cumsum(w / w.sum())
    u = (np.arange(n) + 0.5) / n
    return np.minimum(np.searchsorted(cdf, u), count - 1)


class Traffic:
    """Requests of one mix for one seed, in the order clients send them."""

    def __init__(self, spec: Dict, seed: int, vocab: int):
        self.spec = spec
        self.loop = spec.get("loop", "closed")
        if self.loop not in ("closed", "open"):
            raise ValueError(f"traffic loop must be closed or open, got "
                             f"{self.loop!r}")
        self.clients = int(spec.get("clients", 0))
        self.vocab = vocab
        self.rng = np.random.default_rng(seed)
        order = np.random.default_rng(ORDER_SEED)
        n = int(spec.get("size_pool", 256))
        self.prompt_lens = order.permutation(
            quantiles(spec["prompt_tokens"], n))
        self.output_lens = order.permutation(
            quantiles(spec["output_tokens"], n))
        self.docs: List[List[int]] = []
        self.doc_of = np.full(n, -1)
        if "documents" in spec:
            d = spec["documents"]
            # popularity rank -> length is fixed across seeds, so a seed
            # never makes the popular document the long one
            lens = quantiles({"min": d["min"], "max": d["max"]}, d["count"])
            lens = np.concatenate([lens[::2], lens[1::2][::-1]])
            self.docs = [self._tokens(int(L)) for L in lens]
            self.doc_of = order.permutation(
                zipf_choices(d["count"], d.get("zipf_s", 1.0), n))
        self.greedy_every = int(spec.get("greedy_every", 0))
        self.sent = 0

    def _tokens(self, n: int) -> List[int]:
        return self.rng.integers(0, self.vocab, size=n).tolist()

    def next(self) -> RequestSpec:
        i = self.sent
        self.sent += 1
        k = i % len(self.prompt_lens)
        prompt = self._tokens(int(self.prompt_lens[k]))
        doc = int(self.doc_of[k])
        if doc >= 0:
            prompt = self.docs[doc] + prompt
        greedy = self.greedy_every and i % self.greedy_every == 0
        return RequestSpec(
            index=i, prompt=prompt,
            max_new_tokens=int(self.output_lens[k]),
            temperature=0.0 if greedy else float(self.spec["temperature"]),
            top_p=1.0 if greedy else float(self.spec.get("top_p", 1.0)),
            document=doc)

    def arrivals(self, horizon_s: float) -> np.ndarray:
        """Due times (s from the start of sending) of an open loop."""
        rate = float(self.spec["rate_per_s"])
        gaps = np.random.default_rng(ORDER_SEED).exponential(
            1.0 / rate, size=int(horizon_s * rate * 2) + 16)
        t = np.cumsum(gaps)
        burst: Optional[Dict] = self.spec.get("burst")
        if burst:
            period = float(burst["period_s"])
            extra = np.repeat(np.arange(period, horizon_s, period),
                              int(burst["size"]))
            t = np.concatenate([t, extra])
        t = np.sort(t)
        return t[t < horizon_s]

    def max_total_tokens(self) -> int:
        doc = max((len(d) for d in self.docs), default=0)
        return (doc + int(self.spec["prompt_tokens"]["max"])
                + int(self.spec["output_tokens"]["max"]))
