"""Benchmark runner — one module per paper table/figure.

  fig3_latency    latency vs context, cached vs uncached        (Fig. 3)
  fig4_decode     decode ms/token, paged vs contiguous kernel   (Fig. 4)
  fig12_memory    KV memory accounting, paged vs baseline       (Figs. 1-2)
  tbl_allocator   O(1) RESERVE/FREE microbenchmark              (contrib. 1)
  tbl_decode_blocks  pages_per_block × num_splits kernel sweep  (kernel v2)
  tbl_perplexity  numerical equivalence of eval loss            (§IV-B3)
  mixed_batch     throughput under a fixed memory budget        (§IV b)

Prints ``name,us_per_call,derived`` CSV at the end (harness contract).
"""

from __future__ import annotations

import argparse
import inspect
import sys
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma list of bench names")
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--backend", default=None, choices=["tpu", "gpu"],
                    help="restrict kernel benches to one Pallas lowering "
                         "(default: sweep both where the bench supports it)")
    ap.add_argument("--prefix-cache", dest="prefix_cache", default=None,
                    choices=["on", "off"],
                    help="restrict prefix-cache-aware benches to one mode "
                         "(default: benches report both on and off rows)")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (fig3_latency, fig4_decode, fig12_memory,
                            mixed_batch, tbl_allocator, tbl_decode_blocks,
                            tbl_pagesize, tbl_perplexity)
    benches = {
        "fig3_latency": fig3_latency.run,
        "fig4_decode": fig4_decode.run,
        "fig12_memory": fig12_memory.run,
        "tbl_allocator": tbl_allocator.run,
        "tbl_decode_blocks": tbl_decode_blocks.run,
        "tbl_pagesize": tbl_pagesize.run,
        "tbl_perplexity": tbl_perplexity.run,
        "mixed_batch": mixed_batch.run,
    }
    only = [s for s in args.only.split(",") if s]
    csv = ["name,us_per_call,derived"]
    failed = []
    for name, fn in benches.items():
        if only and name not in only:
            continue
        try:
            kw = {}
            if "backend" in inspect.signature(fn).parameters:
                kw["backend"] = args.backend
            if "prefix_cache" in inspect.signature(fn).parameters:
                kw["prefix_cache"] = args.prefix_cache
            table = fn(fast=args.fast, **kw)
            csv.extend(table.csv_lines())
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()
    print("\n--- CSV ---")
    print("\n".join(csv))
    if failed:
        print(f"\nFAILED: {failed}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
