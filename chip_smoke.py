"""Chip smoke test: serve granite-8b at its published widths on one TPU
through `Engine`, with the Pallas kernels compiled.

    python3 chip_smoke.py

Builds granite-8b (d_model 4096, 32 heads over 8 KV heads, head_dim 128,
d_ff 14336, vocab 49152, page size 64) with bf16 weights drawn from a seed
and its depth cut to 16 of 36 layers, then serves 8 requests (prompts of
128-1536 tokens, 32 new tokens each) twice over:

  * with ``prefill_chunk=512`` — paged prefill kernel + paged decode
    kernel (split-K, fused combine);
  * with monolithic prefill — flex prefill kernel + paged decode kernel.

It fails (non-zero exit, no result line) when no TPU is found, when a
request does not reach FINISHED, when the jitted decode step carries no
``tpu_custom_call`` (kernels interpreted or replaced by the jnp oracle),
or when the kernel path's logits leave the tolerance around the repo's
jnp path (``impl="ref"``, same params, same prompts, same tokens).  The
last line of its output is one JSON object naming the device.

`run_smoke` holds everything but the device checks, so a CPU test can run
it at smoke size with the kernels interpreted.
"""

from __future__ import annotations

import gc
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.base import ModelConfig  # noqa: E402
from repro.models.api import build_model  # noqa: E402
from repro.serving import Engine, Request, Status  # noqa: E402

ARCH = "granite-8b"
DEPTH = 16  # of 36: the full depth (~16 GB in bf16) does not fit one chip
SEED = 0

# Tolerance on the logits of the kernel path against the jnp path, as a
# fraction of the largest |logit| of the reference row.  Both paths run
# bf16 weights and activations with f32 accumulation; they differ only in
# where attention rounds to bf16 (the kernels cast once after an f32
# online softmax, the jnp path casts its softmax weights before P·V, and
# the flex and paged kernels sum in tile order).  Each such rounding is at
# most 2^-8 of the value, and each of the 16 layers adds one to the
# residual stream, so the logits may drift by about 16 · 2^-8 ≈ 6 % of
# their scale; 10 % leaves room for that.  On a TPU v5e the jnp path alone
# moves by about 4 % of scale between the default and the highest matmul
# precision at this depth, so a tighter bound would test rounding, not the
# kernels.  A kernel that reads a wrong page or drops a tile moves the
# logits by their full scale.
LOGIT_TOL = 0.10


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


class _RecordingEngine(Engine):
    """`Engine` that keeps the first ``keep`` logits rows it samples from,
    per request, and appends scripted tokens for requests in ``script``
    instead of sampling — so the reference path sees the same context
    as the kernel path it is compared with."""

    def __init__(self, *args, keep: int, **kw):
        super().__init__(*args, **kw)
        self.keep = keep
        self.logits: Dict[int, List[np.ndarray]] = {}
        self.script: Dict[int, List[int]] = {}

    def _sample_and_append(self, reqs, logits, first):
        rows = np.asarray(jnp.asarray(logits, jnp.float32))
        for r, row in zip(reqs, rows):
            rec = self.logits.setdefault(r.rid, [])
            if len(rec) < self.keep:
                rec.append(row)
        if reqs and all(r.rid in self.script for r in reqs):
            for r in reqs:
                r.output.append(self.script[r.rid][len(r.output)])
            return
        super()._sample_and_append(reqs, logits, first)


def make_prompts(seed: int, n: int, lo: int, hi: int,
                 vocab: int) -> List[List[int]]:
    rng = np.random.default_rng(seed)
    lens = rng.integers(lo, hi + 1, size=n)
    return [rng.integers(0, vocab, size=int(L)).tolist() for L in lens]


def _serve_wave(eng: _RecordingEngine, prompts, max_new: int,
                script: Optional[List[List[int]]] = None) -> List[Request]:
    reqs = [Request(prompt=list(p), max_new_tokens=max_new) for p in prompts]
    if script is not None:
        eng.script.update({r.rid: s for r, s in zip(reqs, script)})
    eng.generate(reqs, max_steps=100 * len(reqs) * (max_new + 1))
    bad = [r.rid for r in reqs if r.status is not Status.FINISHED]
    if bad:
        raise SmokeFailure(f"requests {bad} did not reach FINISHED")
    return reqs


def _decode_text(eng: Engine) -> str:
    """Lowered text of the engine's jitted decode step."""
    st = dict(eng.state)
    st["tables"] = eng._tables_array(decode=True)
    tokens = jnp.zeros((eng.max_slots,), jnp.int32)
    return eng._jit_decode.lower(eng.params, tokens, st).as_text()


def _max_diff(kernel: Dict[int, List[np.ndarray]], kreqs: List[Request],
              ref: Dict[int, List[np.ndarray]], rreqs: List[Request]):
    """Largest |Δlogit| / max|ref logit| over the prefill rows and over
    the decode rows, the largest |Δlogit|, and the rows compared."""
    rel = {"prefill": 0.0, "decode": 0.0}
    worst_abs, rows = 0.0, 0
    for kr, rr in zip(kreqs, rreqs):
        for i, (a, b) in enumerate(zip(kernel[kr.rid], ref[rr.rid])):
            d = float(np.max(np.abs(a - b)))
            kind = "decode" if i else "prefill"
            rel[kind] = max(rel[kind], d / float(np.max(np.abs(b))))
            worst_abs = max(worst_abs, d)
            rows += 1
    return rel, worst_abs, rows


def run_smoke(cfg: ModelConfig, *, dtype=jnp.bfloat16, seed: int = SEED,
              n_requests: int = 8, prompt_lens=(128, 1536),
              max_new: int = 32, max_slots: int = 8,
              max_seq_len: int = 2048, pool_tokens: int = 16384,
              prefill_chunk: int = 512, ref_slots: int = 2,
              compare_steps: int = 4, waves: int = 2,
              tol: float = LOGIT_TOL, log=print) -> Dict:
    """Serve ``n_requests`` through the kernel path in both prefill modes
    and hold its logits (prefill + ``compare_steps - 1`` decode steps per
    request) against the ``impl="ref"`` path.  Raises `SmokeFailure` on
    an unfinished request or a logits difference above ``tol``."""
    t0 = time.perf_counter()
    params = build_model(cfg).init_params(jax.random.PRNGKey(seed), dtype)
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(params))
    report: Dict = {"setup_s": time.perf_counter() - t0,
                    "n_params": n_params, "phases": {}}
    log(f"setup: {n_params / 1e9:.3f}e9 parameters drawn from seed {seed} "
        f"in {report['setup_s']:.1f} s")
    prompts = make_prompts(seed, n_requests, prompt_lens[0], prompt_lens[1],
                           cfg.vocab_size)
    compare_steps = min(compare_steps, max_new)
    common = dict(max_seq_len=max_seq_len, dtype=dtype, keep=compare_steps)

    served = {}
    for name, chunk in ((f"chunked-{prefill_chunk}", prefill_chunk),
                        ("monolithic", None)):
        eng = _RecordingEngine(cfg, params, impl="pallas",
                               max_slots=max_slots, pool_tokens=pool_tokens,
                               prefill_chunk=chunk, **common)
        times, first = [], None
        for _ in range(waves):
            t = time.perf_counter()
            reqs = _serve_wave(eng, prompts, max_new)
            times.append(time.perf_counter() - t)
            first = first or reqs
        tokens = sum(len(r.output) for r in reqs)
        ph = {"wave_s": times, "tokens": tokens,
              "kernel_calls": "tpu_custom_call" in _decode_text(eng)}
        report["phases"][name] = ph
        served[name] = (eng.logits, first)
        log(f"phase {name}: {len(reqs)} requests FINISHED, {tokens} tokens "
            f"per wave; wave times {', '.join(f'{x:.2f}' for x in times)} s "
            f"(first includes compilation)")
        del eng
        gc.collect()  # the jitted step refers back to its engine: free the pool

    ref = _RecordingEngine(cfg, params, impl="ref", max_slots=ref_slots,
                           **common)
    worst = 0.0
    for name, (logits, kreqs) in served.items():
        script = [r.output[:compare_steps] for r in kreqs]
        rreqs = _serve_wave(ref, prompts, compare_steps, script)
        rel, d_abs, rows = _max_diff(logits, kreqs, ref.logits, rreqs)
        d_rel = max(rel.values())
        report["phases"][name].update(logit_abs=d_abs, logit_rel=d_rel)
        log(f"phase {name} vs impl=ref over {rows} rows: largest |logit "
            f"difference| {d_abs:.4g}; as a share of the largest |logit|: "
            f"{rel['prefill']:.4g} on prefill rows, {rel['decode']:.4g} on "
            f"decode rows (tolerance {tol})")
        worst = max(worst, d_rel)
    if worst > tol:
        raise SmokeFailure(f"kernel logits differ from impl=ref by "
                           f"{worst:.4g} of their scale (> {tol})")
    return report


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    cache = Path(enable_compile_cache())
    warm = sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0
    full = get_config(ARCH)
    cfg = full.replace(n_layers=DEPTH)
    print(f"model {ARCH}: depth cut to {DEPTH} of {full.n_layers} layers; "
          f"widths as published: d_model {cfg.d_model}, heads {cfg.n_heads} "
          f"over {cfg.n_kv_heads} KV heads, head_dim {cfg.resolved_head_dim}, "
          f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, page {cfg.page_size}")
    print("dtype: bfloat16 weights, activations and KV pages; "
          "f32 accumulation in the kernels")
    print(f"device: {dev.device_kind} ({dev.platform}), "
          f"{len(jax.devices())} chip(s)")
    print(f"compile cache: {cache} ({warm} entries before this run)")
    t0 = time.perf_counter()
    report = run_smoke(cfg)
    for name, ph in report["phases"].items():
        if not ph["kernel_calls"]:
            raise SmokeFailure(f"phase {name}: the jitted decode step has no "
                               "tpu_custom_call (kernels not compiled)")
    print("decode step: tpu_custom_call present in both phases")
    tokens = sum(ph["tokens"] for ph in report["phases"].values())
    print(f"served {tokens} new tokens per wave over both phases; whole run "
          f"{time.perf_counter() - t0:.1f} s")
    stats = dev.memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        print(f"peak device memory: {stats['peak_bytes_in_use'] / 2**30:.2f} "
              f"GiB of {stats.get('bytes_limit', 0) / 2**30:.2f} GiB")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
