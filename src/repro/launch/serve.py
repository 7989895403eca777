"""Serving launcher: continuous-batching engine over the paged KV cache.

Runs the full engine loop (admission → prefill → paged decode → sampling)
with the Pallas kernels (``--impl pallas``, the default): compiled on a
TPU, interpreted elsewhere.  ``--smoke`` serves the reduced config;
without it the published widths, optionally with the depth cut.

Usage:
  python -m repro.launch.serve --arch granite-8b --smoke --requests 8
  python -m repro.launch.serve --arch granite-8b --layers 16 \
      --dtype bfloat16 --max-seq-len 2048 --pool-tokens 16384
"""

from __future__ import annotations

import argparse
import time

import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.serving import Engine, Request


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--max-seq-len", type=int, default=256)
    ap.add_argument("--pool-tokens", type=int, default=None,
                    help="oversubscribe the page pool (paper's memory win)")
    ap.add_argument("--no-paged", action="store_true",
                    help="contiguous baseline (the paper's comparison)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--impl", default="pallas", choices=["pallas", "ref"],
                    help="attention ops: Pallas kernels or the jnp oracle")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="weights, activations and KV pages")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.layers is not None:
        cfg = cfg.replace(n_layers=args.layers)
    eng = Engine(cfg, max_slots=args.max_slots, max_seq_len=args.max_seq_len,
                 pool_tokens=args.pool_tokens, paged=not args.no_paged,
                 impl=args.impl, dtype=jnp.dtype(args.dtype))

    rng = np.random.default_rng(0)
    reqs, extras = [], []
    for i in range(args.requests):
        plen = int(rng.integers(4, args.max_seq_len - args.max_new))
        prompt = rng.integers(0, min(cfg.vocab_size, 256),
                              size=plen).tolist()
        reqs.append(Request(prompt=prompt, max_new_tokens=args.max_new,
                            temperature=args.temperature))
        extra = None
        if cfg.family == "vlm":
            extra = {"image_embeds": rng.standard_normal(
                (cfg.n_image_tokens, cfg.d_vision), np.float32)}
        elif cfg.family == "encdec":
            extra = {"frames": rng.standard_normal(
                (cfg.n_audio_frames, cfg.d_model), np.float32)}
        extras.append(extra)

    t0 = time.perf_counter()
    eng.generate(reqs, extras=extras)
    wall = time.perf_counter() - t0
    total_new = sum(len(r.output) for r in reqs)
    print(f"\n{args.requests} requests, {total_new} tokens in {wall:.1f}s "
          f"({total_new/wall:.1f} tok/s aggregate)")
    print(f"engine steps: {eng.steps}  preemptions: "
          f"{eng.scheduler.preempted}")
    mr = eng.memory_report()
    print(f"kv pool {mr['pool_bytes']/2**20:.1f} MiB; overhead vs "
          f"theoretical min: {mr['overhead_frac']*100:.1f}%")
    for r in reqs[:4]:
        print(f"  req {r.rid}: prompt {r.prompt_len} -> {len(r.output)} new, "
              f"ttft {r.metrics.get('ttft_s', -1):.3f}s")


if __name__ == "__main__":
    main()
