"""Mean time a request admitted in the window waited for a slot, in ms.

The engine's counters ``robustness_report()["queue_wait_ns"]`` (sum of
admission minus arrival at ``Engine.add_request``, over first
admissions) and ``["admitted"]`` (first admissions), taken before and
after the window.  With more clients than slots the wait is most of the
time to first token, so it should move ``ttft_p50_ms``.
"""


def reduce(run):
    b, a = run.counters["before"], run.counters["after"]
    keys = ("queue_wait_ns", "admitted")
    if any(k not in b or k not in a for k in keys):
        return None
    n = a["admitted"] - b["admitted"]
    return (a["queue_wait_ns"] - b["queue_wait_ns"]) / n / 1e6 if n else None
