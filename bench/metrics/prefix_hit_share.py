"""Share of the prompt tokens admitted in the window that the prefix
cache served, in percent.

The engine's counter ``robustness_report()["prefix_hit_tokens"]`` taken
before and after the window, over the prompt tokens of the requests the
scheduler admitted in it.  Should move ``ttft_p50_ms``.
"""


def reduce(run):
    before = run.counters["before"].get("prefix_hit_tokens")
    after = run.counters["after"].get("prefix_hit_tokens")
    admitted = sum(s.admitted_tokens for s in run.steps)
    if before is None or after is None or not admitted:
        return None
    return 100.0 * (after - before) / admitted
