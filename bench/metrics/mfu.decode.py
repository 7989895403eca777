"""Model FLOP utilization of the decode-only steps, in percent.

Useful FLOPs of the rows those steps decoded (the active parameters and
attention over each row's live context, ``Shape.decode_flops`` of the
architecture's module under ``bench/arch/``) over their wall time on the
host clock times the chip's bf16 peak.  Should move ``itl_p50_ms``.
"""


def reduce(run):
    steps = [s for s in run.steps if s.decode_ctx and not s.chunk_rows]
    wall = sum(s.wall for s in steps)
    if not wall:
        return None
    work = sum(run.shape.decode_flops(c)
               for s in steps for c in s.decode_ctx)
    return 100.0 * work / (wall * run.peak["bf16_flops_per_s"])
