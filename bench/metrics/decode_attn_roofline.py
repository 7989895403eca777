"""Paged decode attention's share of its roofline, in percent.

For every decode step of the window and every layer that runs the
paged kernels (``Shape.attn_layers``), the least time the chip needs for
the live context's key/value bytes and attention FLOPs
(``Shape.decode_attn_cost`` of each decoded row, summed per kernel call,
``flops.roofline_s``), over the device time of the Pallas calls the
trace names ``paged_attention`` (the paged decode kernel, and its fused
split-K combine where one runs).  Should move ``itl_p50_ms``.
"""

import flops
from devtrace import kernel_match

KERNELS = ("paged_attention",)


def reduce(run):
    if run.trace is None:
        return None
    t = run.trace.op_seconds(kernel_match(KERNELS))
    if not t:
        return None
    need = 0.0
    for s in run.steps:
        if s.decode_ctx:
            costs = [run.shape.decode_attn_cost(c) for c in s.decode_ctx]
            need += run.shape.attn_layers * flops.roofline_s(
                sum(f for f, _ in costs), sum(b for _, b in costs),
                run.peak)
    return 100.0 * need / t
