"""Mean time from a request's admission to its first token, in ms, over
the first tokens of the window.

The engine's counters ``robustness_report()["prefill_ns"]`` (sum of
first-token time minus first admission) and ``["first_tokens"]``, taken
before and after the window: the chunked prefill's part of the time to
first token, beside the slot queue's (``queue_wait_ms``).  Should move
``ttft_p50_ms``.
"""


def reduce(run):
    b, a = run.counters["before"], run.counters["after"]
    keys = ("prefill_ns", "first_tokens")
    if any(k not in b or k not in a for k in keys):
        return None
    n = a["first_tokens"] - b["first_tokens"]
    return (a["prefill_ns"] - b["prefill_ns"]) / n / 1e6 if n else None
