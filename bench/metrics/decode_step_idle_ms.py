"""Device idle time per decode-only engine step, in ms.

Over the window's ``engine.step`` spans that hold an
``engine.decode.launch`` span and no ``engine.prefill.model`` span (host
spans the engine opens, ``serving/engine.py``): the span's length minus
the union of device programs inside it, averaged (``step_idle.py``).
The host's share of a decode step, so it should move ``itl_p50_ms``.
"""

import step_idle


def reduce(run):
    return step_idle.mean_idle_ms(run.trace, prefill=False)
