"""The profiler trace of a window, reduced to what the metrics read.

``start``/``stop`` wrap ``jax.profiler`` (Python's own function tracer
off: it would log every call of the engine's host code).  ``load`` reads
the ``.xplane.pb`` it wrote with ``jax.profiler.ProfileData`` into plain
intervals:

- ``modules``: per device, (program, start, end) of each program the
  device ran, from the device plane's ``XLA Modules`` line: the union of
  these is the time the device was busy;
- ``ops``: per device, (``program/op``, start, end) of each operation,
  from the ``XLA Ops`` line, named by the HLO instruction without its
  number (``jit__decode_fn/paged_attention``); loops and calls, whose
  time their inner operations already hold, are left out;
- ``spans``: the benchmark's host spans (``bench.*`` and ``engine.*``,
  from ``jax.profiler.TraceAnnotation``), on the same clock;
- ``window``: the ``bench.window`` span.

All times are nanoseconds on the trace's clock.  ``from_json`` builds the
same object from a recorded reduction, which the tests hold.
"""

from __future__ import annotations

import bisect
import shutil
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[str, float, float]

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")
SPAN_PREFIXES = ("bench.", "engine.")


def start(log_dir: Path) -> None:
    import jax
    shutil.rmtree(log_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    """Merge overlapping [start, end] intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals: List[List[float]], lo: float, hi: float):
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


@dataclass
class Trace:
    modules: Dict[str, List[Interval]]
    ops: Dict[str, List[Interval]]
    spans: List[Interval]
    window: Tuple[float, float]

    def busy(self, device: str) -> List[List[float]]:
        return clip(union((s, e) for _, s, e in self.modules[device]),
                    *self.window)

    def busy_window(self) -> Tuple[float, float]:
        """(seconds in which the device ran a program, averaged over the
        devices; seconds of the window)."""
        lo, hi = self.window
        if not self.modules:
            return 0.0, (hi - lo) / 1e9
        busy = [sum(e - s for s, e in self.busy(d)) for d in self.modules]
        return sum(busy) / len(busy) / 1e9, (hi - lo) / 1e9

    def op_seconds(self, match) -> Optional[float]:
        """Total device time (averaged over devices) of the window's ops
        whose name satisfies ``match``; None when no op does."""
        lo, hi = self.window
        tot, hit = 0.0, False
        for d, ops in self.ops.items():
            for name, s, e in ops:
                if lo <= s < hi and match(name):
                    tot += e - s
                    hit = True
        return tot / max(len(self.ops), 1) / 1e9 if hit else None

    def top_ops(self, n: int = 10) -> List[List]:
        lo, hi = self.window
        tot: Dict[str, float] = {}
        for ops in self.ops.values():
            for name, s, e in ops:
                if lo <= s < hi:
                    tot[name] = tot.get(name, 0.0) + (e - s)
        k = max(len(self.ops), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, t / k / 1e9] for name, t in top]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The longest stretches of the window with no op on the first
        device, each named by the innermost host span open at its
        middle."""
        if not self.modules:
            return []
        lo, hi = self.window
        dev = sorted(self.modules)[0]
        gaps, t = [], lo
        for s, e in self.busy(dev):
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) / 2
            inner = [sp for sp in self.spans if sp[1] <= mid <= sp[2]]
            # of nested spans the innermost starts last and ends first
            name = (max(inner, key=lambda sp: (sp[1], -sp[2]))[0]
                    if inner else "no benchmark span")
            out.append([name, (e - s) / 1e9])
        return out

    def breakdown(self) -> Dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}



def from_json(d: Dict) -> Trace:
    """A trace from its fields as JSON lists: ``modules`` and ``ops`` map a
    device to [name, start, end] triples, ``spans`` is such a list and
    ``window`` a [start, end] pair."""
    def ivs(v):
        return [(n, float(s), float(e)) for n, s, e in v]
    return Trace({k: ivs(v) for k, v in d["modules"].items()},
                 {k: ivs(v) for k, v in d["ops"].items()},
                 ivs(d["spans"]), tuple(d["window"]))


def program_name(event_name: str) -> str:
    """``jit__decode_fn(4248164808701085741)`` -> ``jit__decode_fn``."""
    return event_name.split("(", 1)[0]


def instruction(event_name: str) -> str:
    """``%paged_attention.24 = (f32[...]) custom-call(...)`` ->
    ``paged_attention``: the HLO instruction's name without its number."""
    head = event_name.split(" = ", 1)[0].lstrip("%").strip()
    base, _, num = head.rpartition(".")
    return base if base and num.isdigit() else head


def load(log_dir: Path) -> Trace:
    from jax.profiler import ProfileData
    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(str(files[-1]))
    modules: Dict[str, List[Interval]] = {}
    ops: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = {line.name: line for line in plane.lines}
            if MODULE_LINE not in lines:
                continue
            mods = [(program_name(e.name), e.start_ns,
                     e.start_ns + e.duration_ns)
                    for e in lines[MODULE_LINE].events]
            modules[plane.name] = mods
            ops[plane.name] = _ops(mods, lines.get(OP_LINE))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    win = [s for s in spans if s[0] == "bench.window"]
    if not win:
        raise ValueError("the trace holds no bench.window span")
    return Trace(modules, ops, spans, (win[0][1], win[0][2]))


def _ops(mods: List[Interval], line) -> List[Interval]:
    """The op line's events named ``program/instruction``; containers
    (loops, calls) left out."""
    if line is None:
        return []
    starts = [s for _, s, _ in mods]
    out = []
    for e in line.events:
        inst = instruction(e.name)
        if inst.startswith(CONTAINERS):
            continue
        i = bisect.bisect_right(starts, e.start_ns) - 1
        prog = mods[i][0] if i >= 0 else "?"
        out.append((f"{prog}/{inst}", e.start_ns, e.start_ns + e.duration_ns))
    return out


def kernel_match(names):
    """Predicate: an op (``program/instruction``) is one of the kernels
    whose instruction is named in ``names``."""
    return lambda op: op.rsplit("/", 1)[-1] in names
