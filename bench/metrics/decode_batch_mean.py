"""Output tokens per engine step that produced any, in the window.

Counted from ``Request.output`` after each step: how full the scheduler
keeps the batch.  Should move ``output_tok_per_s``.
"""


def reduce(run):
    busy = [s.tokens for s in run.steps if s.tokens]
    return sum(busy) / len(busy) if busy else None
