"""End-to-end serving driver: batched requests through the paged engine.

The paper's §IV scenario (b): a mixed-length wave of requests served by
continuous batching on an oversubscribed page pool, compared against the
contiguous-baseline engine under the SAME byte budget. Prints throughput,
TTFT percentiles, preemption counts, and the memory ledger.

Run:  PYTHONPATH=src python examples/serve_batch.py [--arch granite-8b]

Off a TPU it serves the reduced smoke config (kernels interpreted).  On a
TPU, ``--full --layers N --dtype bfloat16`` serves the published widths
with the depth cut to N layers and the kernels compiled.
"""

import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.serving import Engine, Request


def wave(rng, n, max_prompt, max_new):
    return [Request(prompt=rng.integers(0, 256,
                                        size=int(rng.integers(8, max_prompt))
                                        ).tolist(),
                    max_new_tokens=max_new) for _ in range(n)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-7b")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--impl", default="pallas", choices=["ref", "pallas"],
                    help="attention ops: Pallas kernels or the jnp oracle")
    ap.add_argument("--full", action="store_true",
                    help="published widths instead of the smoke config")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="weights, activations and KV pages")
    ap.add_argument("--pages-per-block", type=int, default=None,
                    help="Pallas kernel KV-block width (default: auto)")
    ap.add_argument("--num-splits", type=int, default=None,
                    help="Pallas kernel split-K factor (default: auto)")
    ap.add_argument("--combine-mode", default=None,
                    choices=["jnp", "pallas"],
                    help="split-K merge: fused Pallas combine kernel or "
                         "jnp epilogue (default: auto — pallas iff split-K)")
    ap.add_argument("--backend", default=None, choices=["tpu", "gpu"],
                    help="Pallas kernel lowering: TPU scalar-prefetch "
                         "pipeline or GPU/Triton in-kernel gather "
                         "(default: auto from jax.default_backend())")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="prompt tokens prefilled per engine step "
                         "(chunked continuous batching: prompts cache "
                         "chunk-by-chunk interleaved with decode, so a "
                         "long prompt never stalls the running batch; "
                         "default: whole prompt in one monolithic pass)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable the global radix prefix cache, and give "
                         "the wave a shared 48-token system-prompt head: "
                         "requests admitted after the first slot wave "
                         "attach to the cached head pages and prefill "
                         "only their own tail")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.smoke()
    if args.layers is not None:
        cfg = cfg.replace(n_layers=args.layers)
    dtype = jnp.dtype(args.dtype)
    slots, max_seq, pool = 8, 128, 640
    rng = np.random.default_rng(0)

    chunk = ("monolithic" if args.prefill_chunk is None
             else f"{args.prefill_chunk} tok/step")
    print(f"== paged engine: {slots} slots, pool {pool} tokens, "
          f"impl={args.impl}, prefill={chunk} ==")
    eng = Engine(cfg, max_slots=slots, max_seq_len=max_seq,
                 pool_tokens=pool, impl=args.impl, dtype=dtype,
                 pages_per_block=args.pages_per_block,
                 num_splits=args.num_splits,
                 combine_mode=args.combine_mode,
                 backend=args.backend,
                 prefill_chunk=args.prefill_chunk,
                 prefix_cache=args.prefix_cache)
    head = [7] * 48 if args.prefix_cache else []
    reqs = wave(rng, args.requests,
                max_seq - args.max_new - len(head), args.max_new)
    for r in reqs:
        r.prompt = head + r.prompt
    t0 = time.perf_counter()
    eng.generate(reqs, max_steps=3000)
    wall = time.perf_counter() - t0
    new_toks = sum(len(r.output) for r in reqs)
    ttfts = sorted(r.metrics["ttft_s"] for r in reqs)
    print(f"{new_toks} tokens in {wall:.1f}s = {new_toks/wall:.2f} tok/s; "
          f"ttft p50 {ttfts[len(ttfts)//2]:.2f}s "
          f"p95 {ttfts[int(len(ttfts)*0.95)]:.2f}s; "
          f"preemptions {eng.scheduler.preempted}; "
          f"prefill stalls {eng.scheduler.prefill_stalls}")
    print(eng.memory_report())
    if args.prefix_cache:
        rep = eng.robustness_report()
        print(f"prefix cache: {rep['prefix_hits']} hits / "
              f"{rep['prefix_misses']} misses, "
              f"{rep['prefix_hit_tokens']} prompt tokens skipped "
              f"({rep['prefix_hit_tokens'] // cfg.page_size} pages), "
              f"{rep['prefix_evicted_pages']} pages evicted")

    # contiguous baseline under the same KV byte budget -> fewer slots
    slots_c = max(1, pool // max_seq)
    print(f"\n== contiguous baseline: {slots_c} slots (same bytes) ==")
    eng2 = Engine(cfg, params=eng.params, paged=False, max_slots=slots_c,
                  max_seq_len=max_seq, dtype=dtype)
    reqs2 = wave(np.random.default_rng(0), args.requests,
                 max_seq - args.max_new, args.max_new)
    t0 = time.perf_counter()
    eng2.generate(reqs2, max_steps=3000)
    wall2 = time.perf_counter() - t0
    new2 = sum(len(r.output) for r in reqs2)
    print(f"{new2} tokens in {wall2:.1f}s = {new2/wall2:.2f} tok/s")
    print(f"\npaged speedup at equal memory: {new_toks/wall/(new2/wall2):.2f}x")


if __name__ == "__main__":
    main()
