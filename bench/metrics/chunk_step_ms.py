"""Mean wall time of the engine steps that carried a prompt chunk.

Host clock around ``Engine.step``; a step carried a chunk when some
request's prefill position advanced in it.  Such a step holds up every
decoding row behind it, so it should move ``itl_p99_ms``.
"""


def reduce(run):
    walls = [s.wall for s in run.steps if s.chunk_rows]
    return 1e3 * sum(walls) / len(walls) if walls else None
