"""Model FLOP utilization of the whole window, in percent.

All useful FLOPs the window's steps did (prompt chunks and decoded rows)
over the window's length on the host clock times the chip's bf16 peak.
Bounds every kernel's share from above.  Should move ``output_tok_per_s``.
"""


def reduce(run):
    work = sum(run.shape.decode_flops(c)
               for s in run.steps for c in s.decode_ctx)
    work += sum(run.shape.prefill_flops(a, n)
                for s in run.steps for a, n in s.chunk_rows)
    if not run.steps:
        return None
    return 100.0 * work / ((run.w1 - run.w0) * run.peak["bf16_flops_per_s"])
