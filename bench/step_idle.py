"""Device idle time inside the engine's own step spans.

``Engine.step`` runs inside a host span ``engine.step``; a step that
prefilled holds an ``engine.prefill.model`` span around the model call,
and a step that decoded an ``engine.decode.launch`` span around the
decode step's launch.  A step's idle time is its span's length minus the
part of it in which the device ran a program.  The names are matched as
written here, not imported from the engine: a renamed span reads as no
span.
"""

from __future__ import annotations

import bisect
from typing import Optional

STEP = "engine.step"
PREFILL = "engine.prefill.model"
DECODE = "engine.decode.launch"


def _busy_in(starts, busy, s, e) -> float:
    """Length of [s, e] covered by ``busy`` (sorted, disjoint [start, end]
    pairs whose starts are ``starts``)."""
    i = max(bisect.bisect_right(starts, s) - 1, 0)
    tot = 0.0
    while i < len(busy) and busy[i][0] < e:
        tot += max(0.0, min(busy[i][1], e) - max(busy[i][0], s))
        i += 1
    return tot


def mean_idle_ms(trace, prefill: bool) -> Optional[float]:
    """Mean device idle in ms, averaged over the devices, of the window's
    ``engine.step`` spans that hold a prefill model call (``prefill``) or
    that hold a decode launch and no prefill model call (not
    ``prefill``).  None without a trace, a device or such a step."""
    if trace is None or not trace.modules:
        return None
    lo, hi = trace.window
    steps = sorted((s, e) for n, s, e in trace.spans
                   if n == STEP and lo <= s and e <= hi)
    inner = {n: sorted(s for m, s, _ in trace.spans if m == n)
             for n in (PREFILL, DECODE)}

    def holds(name, s, e):
        starts = inner[name]
        i = bisect.bisect_left(starts, s)
        return i < len(starts) and starts[i] <= e

    picked = [(s, e) for s, e in steps
              if holds(PREFILL, s, e) == prefill
              and (prefill or holds(DECODE, s, e))]
    if not picked:
        return None
    idle = 0.0
    for dev in trace.modules:
        busy = trace.busy(dev)
        starts = [b[0] for b in busy]
        idle += sum(e - s - _busy_in(starts, busy, s, e) for s, e in picked)
    return idle / len(trace.modules) / len(picked) / 1e6
