"""Serving benchmark: one cell, one seed, one run, on a TPU.

    python3 bench/serve.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Builds the cell named in ``BENCHMARK.json`` (its model configuration from
``bench/configs/``, its traffic mix from ``bench/traffic/``), draws the
weights and the traffic from ``--seed``, warms up, serves the mix through
``Engine.add_request`` / ``Engine.step`` for ``--seconds``, drains, and
checks the greedy requests' tokens against the plain float32 reference.
The last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics, or
with ``--trace 1`` its per-layer ones read from a profiler trace of the
window), ``device`` and last ``checks``, each number compared beside its
limit.  The checks are also the last lines on standard error.

It exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.  ``--control 1`` holds the fp8 control's
numbers in place of the engine's, so that its line reads not correct (for
setting the limits; the benchmark's runs leave it off).
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(1, str(ROOT / "src"))
# the persistent compile cache lives inside the checkout, at a fixed path
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"serve: no TPU found (JAX platform {devs[0].platform!r})",
              file=sys.stderr)
        return 2
    import harness
    cell = harness.load_cell(args.workload)
    if len(devs) < cell.workload["chips"]:
        print(f"serve: the cell needs {cell.workload['chips']} chips, "
              f"JAX finds {len(devs)}", file=sys.stderr)
        return 2

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    # persist every program, however quick to compile: the eager prefill
    # dispatches hundreds of small ones that would otherwise compile anew
    # in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    result = harness.run_cell(args.workload, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START,
                              control=bool(args.control), log=log)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
