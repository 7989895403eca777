"""Sharding rules, sampler, and distributed decode-scheme plumbing."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.distributed.sharding import (AxisRules, DEFAULT_RULES,
                                        logical_spec, use_mesh)
from repro.launch.mesh import make_local_mesh
from repro.serving.sampler import SampleParams, sample


# ---------------------------------------------------------------------------
# logical-axis rules
# ---------------------------------------------------------------------------
def test_logical_spec_basic():
    rules = AxisRules({"batch": ("pod", "data"), "heads": "model"})
    spec = logical_spec(("batch", None, "heads"), rules, mesh=None)
    assert spec == P(("pod", "data"), None, "model")


def test_logical_spec_drops_duplicate_mesh_axes():
    rules = AxisRules({"seq": ("model",), "vocab": ("model",)})
    spec = logical_spec(("seq", "vocab"), rules, mesh=None)
    # first occurrence wins, second is replicated
    assert spec == P("model")


def test_logical_spec_divisibility_fallback():
    mesh = make_local_mesh()
    rules = AxisRules({"heads": ("model",)})
    # 1-device mesh: any size divides; now a fake check with shape
    spec = logical_spec(("heads",), rules, mesh=mesh, shape=(7,))
    assert spec == P("model")  # 7 % 1 == 0


def test_config_overrides_extend_rules():
    rules = DEFAULT_RULES.extend(embed=("data",))
    assert rules.physical("embed") == ("data",)
    assert rules.physical("heads") == ("model",)


def test_plan_scheme_selection():
    from repro.configs import get_config, make_run
    from repro.launch.steps import plan_for
    import os
    mesh = make_local_mesh()
    # kv=8 % model=1 == 0 -> tp on a 1-wide model axis
    run = make_run(get_config("granite-8b"), "decode_32k")
    assert plan_for(run, mesh).scheme == "tp"


def test_use_mesh_restores_context():
    from repro.distributed.sharding import current_mesh
    mesh = make_local_mesh()
    assert current_mesh() is None
    with use_mesh(mesh):
        assert current_mesh() is mesh
    assert current_mesh() is None


def test_train_step_under_local_mesh(rng):
    """The pjit path end-to-end on a 1-device mesh with production rules."""
    from repro.configs import get_smoke
    from repro.configs.base import RunConfig
    from repro.launch.steps import build_step, plan_for
    from repro.training.state import TrainState

    cfg = get_smoke("olmoe-1b-7b")
    run = RunConfig(model=cfg, seq_len=16, global_batch=2, kind="train")
    mesh = make_local_mesh()
    plan = plan_for(run, mesh, attn_impl="jnp")
    step, abstract, shardings, model = build_step(run, plan,
                                                  dtype=jnp.float32)
    with use_mesh(mesh, plan.rules):
        params = model.init_params(rng)
        state = TrainState.create(params)
        batch = {"inputs": jnp.ones((2, 16), jnp.int32),
                 "targets": jnp.ones((2, 16), jnp.int32)}
        state, metrics = jax.jit(step)(state, batch)
    assert np.isfinite(float(metrics["loss"]))


def test_serve_step_under_local_mesh(rng):
    from repro.configs import get_smoke
    from repro.configs.base import RunConfig
    from repro.launch.steps import build_step, plan_for

    cfg = get_smoke("granite-8b")
    run = RunConfig(model=cfg, seq_len=32, global_batch=2, kind="decode")
    mesh = make_local_mesh()
    plan = plan_for(run, mesh)
    step, abstract, shardings, model = build_step(run, plan,
                                                  dtype=jnp.float32)
    with use_mesh(mesh, plan.rules):
        params = model.init_params(rng)
        state = model.init_decode_state(run, n_kv_shards=plan.n_kv_shards)
        b, n_sh, pps = state["tables"].shape
        state["tables"] = jnp.arange(b * n_sh * pps,
                                     dtype=jnp.int32).reshape(b, n_sh, pps)
        state["pos"] = jnp.asarray([5, 3], jnp.int32)
        logits, st = jax.jit(step)(params, jnp.asarray([1, 2], jnp.int32),
                                   state)
    assert logits.shape == (2, cfg.vocab_size)
    assert not np.isnan(np.asarray(logits)).any()


# ---------------------------------------------------------------------------
# sampler
# ---------------------------------------------------------------------------
def test_sampler_greedy(rng):
    logits = jnp.asarray([[0.1, 3.0, -1.0], [2.0, 0.0, 1.0]])
    p = SampleParams(temperature=jnp.zeros(2), top_k=jnp.zeros(2, jnp.int32),
                     top_p=jnp.ones(2))
    toks = sample(rng, logits, p)
    assert toks.tolist() == [1, 0]


def test_sampler_top_k_restricts_support(rng):
    logits = jnp.asarray([[0.0, 1.0, 2.0, 3.0]] * 64, jnp.float32)
    p = SampleParams(temperature=jnp.full((64,), 1.0),
                     top_k=jnp.full((64,), 2, jnp.int32),
                     top_p=jnp.ones((64,)))
    toks = np.asarray(sample(rng, logits, p))
    assert set(toks.tolist()) <= {2, 3}


def test_sampler_top_p_keeps_argmax(rng):
    logits = jnp.asarray([[10.0, 0.0, 0.0, 0.0]] * 16, jnp.float32)
    p = SampleParams(temperature=jnp.full((16,), 1.0),
                     top_k=jnp.zeros((16,), jnp.int32),
                     top_p=jnp.full((16,), 0.1))
    toks = np.asarray(sample(rng, logits, p))
    assert (toks == 0).all()


def test_sampler_temperature_diversity(rng):
    logits = jnp.zeros((128, 8), jnp.float32)  # uniform
    p = SampleParams(temperature=jnp.full((128,), 1.0),
                     top_k=jnp.zeros((128,), jnp.int32),
                     top_p=jnp.ones((128,)))
    toks = np.asarray(sample(rng, logits, p))
    assert len(set(toks.tolist())) >= 4
