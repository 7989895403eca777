"""Paged prefill attention's share of its roofline, in percent.

For every prompt chunk of the window and every layer that runs the
paged kernels (``Shape.attn_layers``), the least time the chip needs for
the attention FLOPs of the live prompt tokens and the key/value bytes
of their context (``Shape.prefill_attn_cost`` summed per kernel call,
``flops.roofline_s``), over the device time of the Pallas calls the
trace names ``paged_prefill``.  Should move ``ttft_p50_ms``.
"""

import flops
from devtrace import kernel_match

KERNELS = ("paged_prefill",)


def reduce(run):
    if run.trace is None:
        return None
    t = run.trace.op_seconds(kernel_match(KERNELS))
    if not t:
        return None
    need = 0.0
    for s in run.steps:
        if s.chunk_rows:
            costs = [run.shape.prefill_attn_cost(a, n)
                     for a, n in s.chunk_rows]
            need += run.shape.attn_layers * flops.roofline_s(
                sum(f for f, _ in costs), sum(b for _, b in costs),
                run.peak)
    return 100.0 * need / t
