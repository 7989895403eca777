"""Step factories + sharding plans for every (run × mesh) combination.

This is the single source of truth the multi-pod dry-run, the train/serve
drivers, and the roofline harness all share: given a ``RunConfig`` and a
mesh, build

  * the jit-able step function (train / prefill / serve),
  * abstract inputs (ShapeDtypeStructs — no device allocation),
  * in/out shardings for every input,

so ``jax.jit(fn, in_shardings=...).lower(**abstract).compile()`` is the
whole dry-run.

Sharding plan summary (DESIGN.md §4):
  train/prefill — GSPMD: batch over ("pod","data"), sequence-parallel
    activations over "model" between blocks, TP weights over "model",
    FSDP "embed" over "data" for ≥8B models (config override).
  decode — shard_map schemes: "tp" (kv heads over model), "dp" (bounded
    ring pools, kv replicated), "kvp" (pages striped over model,
    flash-decoding psum combine).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig
from repro.distributed.sharding import (AxisRules, DEFAULT_RULES,
                                        make_param_shardings, use_mesh)
from repro.models.api import build_model
from repro.training.loop import make_train_step
from repro.training.optimizer import AdamWState
from repro.training.state import TrainState


@dataclass
class Plan:
    run: RunConfig
    mesh: Mesh
    rules: AxisRules
    batch_axes: Tuple[str, ...]
    scheme: str  # decode distribution scheme: local | tp | dp | kvp
    kv_axes: Tuple[str, ...]
    microbatches: int
    attn_impl: str
    zero_pod: bool = False
    notes: Dict[str, Any] = field(default_factory=dict)

    @property
    def n_kv_shards(self) -> int:
        return _mesh_prod(self.mesh, self.kv_axes) if self.scheme == "kvp" else 1


def _mesh_prod(mesh: Mesh, axes: Tuple[str, ...]) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return math.prod(sizes[a] for a in axes) if axes else 1


def plan_for(run: RunConfig, mesh: Mesh, *,
             microbatches: Optional[int] = None,
             attn_impl: str = "chunked",
             scheme: Optional[str] = None,
             seq_parallel: bool = True,
             ws_decode: bool = False,
             ring: bool = False,
             zero_pod: bool = False) -> Plan:
    cfg = run.model
    rules = DEFAULT_RULES.extend(**cfg.axis_overrides)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    model_size = sizes.get("model", 1)

    # batch axes: use only as much of (pod, data) as the batch divides
    cand = tuple(a for a in ("pod", "data") if a in sizes)
    batch_axes: Tuple[str, ...] = ()
    prod = 1
    for a in cand:
        if run.global_batch % (prod * sizes[a]) == 0:
            batch_axes += (a,)
            prod *= sizes[a]
    if ws_decode and run.kind == "decode":
        # weight-stationary decode (§Perf H3): keep the 2D-sharded weights
        # in place and psum small activation partials over "data" instead
        # of all-gathering FSDP weight shards every token
        batch_axes = ()
        prod = 1
        rules = rules.extend(batch=None, act_embed=("data",))
    else:
        rules = rules.extend(batch=batch_axes or None)

    if ring and run.kind in ("train", "prefill") \
            and run.seq_len % model_size == 0:
        # ring attention (§Perf H2): activations stay seq-sharded through
        # attention. For inference (no weight grads) q/k/v keep full heads
        # — GSPMD gathers the MB-scale weight shards instead of the
        # GB-scale activations. For training, replicated qkv weights would
        # un-shard their f32 gradients (+66 GiB/dev at 405B — measured,
        # `--tag ring_train`); keep heads sharded and let GSPMD insert the
        # head↔seq all-to-all at the ring boundary (Ulysses-style).
        rules = rules.extend(seq=("model",), attn_seq=("model",))
        if run.kind == "prefill":
            rules = rules.extend(heads=None, kv_heads=None)
        attn_impl = "ring"

    if run.kind == "train":
        # sequence parallelism: activations shard over "model" between blocks
        if seq_parallel and run.seq_len % model_size == 0:
            rules = rules.extend(seq=("model",))
        if microbatches is None:
            # keep per-device f32 logits under ~256 MB
            vocab_shards = model_size if cfg.vocab_size % model_size == 0 else 1
            per_dev = (run.global_batch * run.seq_len // max(prod, 1)
                       * cfg.vocab_size // vocab_shards * 4)
            microbatches = 1
            while per_dev / microbatches > 256e6 and \
                    run.global_batch % (microbatches * 2 * prod) == 0:
                microbatches *= 2
        sch = "n/a"
        kv_axes: Tuple[str, ...] = ()
    else:
        if (run.kind == "prefill" and seq_parallel
                and run.seq_len % model_size == 0):
            rules = rules.extend(seq=("model",))
        microbatches = 1
        if run.kind == "prefill":
            # prefill pools: pages × batch-axes, head_dim × "model" — the
            # layout write_prefill_sharded scatters into locally; decode's
            # kvp striping is a phase-boundary reshard (DESIGN.md §4)
            return Plan(run=run, mesh=mesh, rules=rules,
                        batch_axes=batch_axes, scheme="prefill_local",
                        kv_axes=(), microbatches=1, attn_impl=attn_impl)
        window = cfg.window if "W" in cfg.pattern() else 0
        sch = scheme or cfg.decode_scheme
        if sch in ("auto", "n/a"):
            if cfg.n_kv_heads % model_size == 0:
                sch = "tp"
            elif window > 0:
                sch = "dp"
            else:
                sch = "kvp"
        if sch == "tp" and cfg.n_kv_heads % model_size != 0:
            sch = "kvp"
        if sch == "kvp" and window > 0:
            sch = "dp"
        kv_axes = (tuple(a for a in mesh.axis_names if a not in batch_axes)
                   if sch == "kvp" else ())

    return Plan(run=run, mesh=mesh, rules=rules, batch_axes=batch_axes,
                scheme=sch, kv_axes=kv_axes, microbatches=microbatches,
                attn_impl=attn_impl, zero_pod=zero_pod)


# ---------------------------------------------------------------------------
# sharding trees
# ---------------------------------------------------------------------------
def _ns(plan: Plan, *axes) -> NamedSharding:
    return NamedSharding(plan.mesh, P(*axes))


def _param_shardings(model, plan: Plan, dtype):
    return make_param_shardings(plan.mesh, plan.rules, model.param_axes(),
                                model.abstract_params(dtype))


def train_state_shardings(model, plan: Plan, dtype=jnp.bfloat16,
                          zero_pod: bool = False):
    p = _param_shardings(model, plan, dtype)
    scalar = _ns(plan)
    mom = p
    if zero_pod and "pod" in plan.mesh.axis_names:
        # ZeRO-1 over the pod axis: optimizer moments additionally shard
        # their "embed" dim across pods (params stay pod-replicated; the
        # update's reshard is the classic ZeRO gather, amortised per step)
        emb = tuple(plan.rules.physical("embed") or ())
        if "pod" not in emb:
            mom_rules = plan.rules.extend(embed=("pod",) + emb)
            mom = make_param_shardings(plan.mesh, mom_rules,
                                       model.param_axes(),
                                       model.abstract_params(dtype))
    return TrainState(params=p, opt=AdamWState(mu=mom, nu=mom, count=scalar),
                      step=scalar)


def batch_shardings(run: RunConfig, plan: Plan) -> Dict[str, NamedSharding]:
    ba = plan.batch_axes or None
    out = {"inputs": _ns(plan, ba, None), "targets": _ns(plan, ba, None)}
    cfg = run.model
    if cfg.family == "vlm":
        out["image_embeds"] = _ns(plan, ba, None, None)
    if cfg.family == "encdec":
        out["frames"] = _ns(plan, ba, None, None)
    return out


def decode_state_shardings(model, plan: Plan, state_abstract) -> Dict:
    """Shardings for the decode/prefill state dict, keyed like the state."""
    ba = plan.batch_axes or None
    page_axes: Tuple[str, ...] = tuple(plan.batch_axes)
    if plan.scheme == "kvp":
        page_axes += plan.kv_axes
    pa = page_axes or None
    kv = plan.kv_axes or None

    out: Dict[str, Any] = {}
    for key, val in state_abstract.items():
        if key == "pos":
            out[key] = _ns(plan, ba)
        elif key in ("k_pages", "v_pages"):
            if plan.scheme == "prefill_local":
                # pages × batch axes, head_dim × model (shard-local writes)
                msz = (_mesh_prod(plan.mesh, ("model",))
                       if "model" in plan.mesh.axis_names else 0)
                hd = "model" if msz and val.shape[-1] % msz == 0 else None
                out[key] = _ns(plan, None, pa, None, None, hd)
                continue
            # tp: kv-head dim over "model"; kvp: pages striped over kv axes
            kvh = "model" if plan.scheme == "tp" else None
            out[key] = _ns(plan, None, pa, kvh, None, None)
        elif key == "tables":
            out[key] = _ns(plan, ba, kv if plan.scheme == "kvp" else None,
                           None)
        elif key in ("cross_k", "cross_v"):
            out[key] = _ns(plan, None, ba, None, None, None)
        elif key in ("k_buf", "v_buf"):
            out[key] = _ns(plan, None, ba, None, None, None)
        elif key == "rec":
            out[key] = jax.tree_util.tree_map(
                lambda a: _ns(plan, None, ba,
                              *(None,) * (len(a.shape) - 2)), val)
        else:
            out[key] = _ns(plan)
    return out


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------
def abstract_batch(run: RunConfig, dtype=jnp.bfloat16) -> Dict[str, Any]:
    cfg = run.model
    B, S = run.global_batch, run.seq_len
    out = {"inputs": jax.ShapeDtypeStruct((B, S), jnp.int32),
           "targets": jax.ShapeDtypeStruct((B, S), jnp.int32)}
    if cfg.family == "vlm":
        out["image_embeds"] = jax.ShapeDtypeStruct(
            (B, cfg.n_image_tokens, cfg.d_vision), dtype)
    if cfg.family == "encdec":
        out["frames"] = jax.ShapeDtypeStruct(
            (B, cfg.n_audio_frames, cfg.d_model), dtype)
    return out


def abstract_train_state(model, dtype=jnp.bfloat16,
                         moment_dtype=None) -> TrainState:
    p = model.abstract_params(dtype)
    mdt = moment_dtype or dtype
    mom = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, mdt), p)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    return TrainState(params=p, opt=AdamWState(mu=mom, nu=mom, count=scalar),
                      step=scalar)


# ---------------------------------------------------------------------------
# step builders (return fn, kwargs-of-abstract-args, in_shardings dict)
# ---------------------------------------------------------------------------
def build_train_step(run: RunConfig, plan: Plan, dtype=jnp.bfloat16,
                     moment_dtype=None):
    model = build_model(run.model)
    base = make_train_step(model, lr=3e-4, impl=plan.attn_impl)
    mb = plan.microbatches

    if mb == 1:
        step = base
    else:
        from repro.training.optimizer import adamw_update, clip_by_global_norm

        def step(state: TrainState, batch: Dict):
            def split(x):
                return x.reshape((mb, x.shape[0] // mb) + x.shape[1:])

            mbatch = {k: split(v) for k, v in batch.items()}

            def loss_of(p, b):
                loss, parts = model.loss_fn(p, b, impl=plan.attn_impl)
                return loss, parts

            def acc_body(carry, b):
                g_acc, loss_acc = carry
                (loss, _), g = jax.value_and_grad(loss_of, has_aux=True)(
                    state.params, b)
                g_acc = jax.tree_util.tree_map(
                    lambda a, x: a + x.astype(jnp.float32), g_acc, g)
                return (g_acc, loss_acc + loss), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (grads, loss), _ = jax.lax.scan(acc_body, (g0, jnp.float32(0.0)),
                                            mbatch)
            grads = jax.tree_util.tree_map(lambda g: g / mb, grads)
            loss = loss / mb
            grads, gnorm = clip_by_global_norm(grads, 1.0)
            new_p, new_opt = adamw_update(grads, state.opt, state.params,
                                          lr=3e-4)
            return (TrainState(new_p, new_opt, state.step + 1),
                    {"loss": loss, "grad_norm": gnorm})

    st_sh = train_state_shardings(model, plan, dtype,
                                  zero_pod=plan.zero_pod)
    b_sh = batch_shardings(run, plan)
    args = {"state": abstract_train_state(model, dtype, moment_dtype),
            "batch": abstract_batch(run, dtype)}
    shardings = {"state": st_sh, "batch": b_sh}
    return step, args, shardings, model


def build_prefill_step(run: RunConfig, plan: Plan, dtype=jnp.bfloat16):
    model = build_model(run.model)
    cfg = run.model
    B, S = run.global_batch, run.seq_len
    state_abs = model.init_decode_state(run, dtype=dtype,
                                        n_kv_shards=plan.n_kv_shards,
                                        abstract=True)
    ba = plan.batch_axes or None

    def step(params, tokens, lens, state, extra=None):
        fn = getattr(model, "prefill_scanned", model.prefill)
        logits, st = fn(params, tokens, state, lens=lens, extra=extra,
                        impl=plan.attn_impl)
        return logits, st

    args: Dict[str, Any] = {
        "params": model.abstract_params(dtype),
        "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
        "lens": jax.ShapeDtypeStruct((B,), jnp.int32),
        "state": state_abs,
    }
    shardings: Dict[str, Any] = {
        "params": _param_shardings(model, plan, dtype),
        "tokens": _ns(plan, ba, None),
        "lens": _ns(plan, ba),
        "state": decode_state_shardings(model, plan, state_abs),
    }
    if cfg.family == "vlm":
        args["extra"] = {"image_embeds": jax.ShapeDtypeStruct(
            (B, cfg.n_image_tokens, cfg.d_vision), dtype)}
        shardings["extra"] = {"image_embeds": _ns(plan, ba, None, None)}
    elif cfg.family == "encdec":
        args["extra"] = {"frames": jax.ShapeDtypeStruct(
            (B, cfg.n_audio_frames, cfg.d_model), dtype)}
        shardings["extra"] = {"frames": _ns(plan, ba, None, None)}
    return step, args, shardings, model


def build_serve_step(run: RunConfig, plan: Plan, dtype=jnp.bfloat16):
    """Decode: ONE new token per sequence against a seq_len KV cache."""
    model = build_model(run.model)
    B = run.global_batch
    state_abs = model.init_decode_state(run, dtype=dtype,
                                        n_kv_shards=plan.n_kv_shards,
                                        abstract=True)
    ba = plan.batch_axes or None
    attn_ctx = {"scheme": plan.scheme, "batch_axes": plan.batch_axes}

    def step(params, tokens, state):
        return model.decode_step(params, tokens, state,
                                 impl="ref", attn_ctx=attn_ctx)

    args = {
        "params": model.abstract_params(dtype),
        "tokens": jax.ShapeDtypeStruct((B,), jnp.int32),
        "state": state_abs,
    }
    shardings = {
        "params": _param_shardings(model, plan, dtype),
        "tokens": _ns(plan, ba),
        "state": decode_state_shardings(model, plan, state_abs),
    }
    return step, args, shardings, model


def build_step(run: RunConfig, plan: Plan, dtype=jnp.bfloat16):
    if run.kind == "train":
        return build_train_step(run, plan, dtype)
    if run.kind == "prefill":
        return build_prefill_step(run, plan, dtype)
    return build_serve_step(run, plan, dtype)


def lower_step(run: RunConfig, plan: Plan, dtype=jnp.bfloat16):
    """Trace + lower (no compile). Returns (lowered, model)."""
    step, args, shardings, model = build_step(run, plan, dtype)
    names = list(args)
    in_sh = tuple(shardings[n] for n in names)
    donate = tuple(i for i, n in enumerate(names) if n == "state")

    with use_mesh(plan.mesh, plan.rules):
        jitted = jax.jit(step, in_shardings=in_sh, donate_argnums=donate)
        lowered = jitted.lower(*(args[n] for n in names))
    return lowered, model
