"""Device idle time per engine step that ran a prefill, in ms.

Over the window's ``engine.step`` spans that hold an
``engine.prefill.model`` span (host spans the engine opens,
``serving/engine.py``): the span's length minus the union of device
programs inside it, averaged (``step_idle.py``).  The host's share of
the steps that hold decoding rows longest, so it should move
``itl_p99_ms``.
"""

import step_idle


def reduce(run):
    return step_idle.mean_idle_ms(run.trace, prefill=True)
