"""Model FLOP utilization of the steps that carried a prompt chunk, in
percent.

Useful FLOPs of the live prompt tokens those steps prefilled
(``Shape.prefill_flops``, causal over the cached prefix) over their wall
time on the host clock times the chip's bf16 peak.  The decode rows those
steps also carry are not counted.  Should move ``ttft_p50_ms``.
"""


def reduce(run):
    steps = [s for s in run.steps if s.chunk_rows]
    wall = sum(s.wall for s in steps)
    if not wall:
        return None
    work = sum(run.shape.prefill_flops(a, n)
               for s in steps for a, n in s.chunk_rows)
    return 100.0 * work / (wall * run.peak["bf16_flops_per_s"])
