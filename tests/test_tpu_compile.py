"""Compile the serving path's kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles at granite-8b widths (bf16,
8 KV heads, GQA group 4, head_dim 128, page size 64) for one chip of a
described ``v5e:2x2`` topology, so the TPU compiler refuses here what it
would refuse on the chip (block shapes off the (8, 128) tiling, vector
ops Mosaic cannot legalize).  The topology is described inside a fixture,
never at import: only the worker that runs this file loads the TPU
library.
"""

import functools
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core.attention import prefill_attention
from repro.kernels.paged_attention import ops
from repro.kernels.paged_attention.paged_attention import (
    combine_partials_pallas, paged_attention_kernel, paged_prefill_kernel)
from repro.models.api import build_model
from repro.serving import Engine

B, N_KV, G, D, PAGE = 8, 8, 4, 128, 64
NUM_PAGES, MAX_PAGES = 256, 32  # 16k-token pool, 2048-token tables
DEVTRACE = Path(__file__).resolve().parents[1] / "bench" / "devtrace.py"


def kernel_names(text: str) -> set:
    """The Pallas calls of a compiled program, each named as the
    benchmark's trace reduction (``bench/devtrace.py``) names its op: the
    roofline metrics find the decode and prefill kernels by these names."""
    if "bench_devtrace" not in sys.modules:
        spec = importlib.util.spec_from_file_location("bench_devtrace",
                                                      DEVTRACE)
        sys.modules[spec.name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(sys.modules[spec.name])
    instruction = sys.modules["bench_devtrace"].instruction
    return {instruction(line.strip()) for line in text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes) -> str:
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _pool():
    return ((NUM_PAGES, N_KV, PAGE, D), jnp.bfloat16)


@pytest.mark.parametrize("ppb,ns", [(1, 1), (2, 1), (1, 4), (2, 4)])
def test_paged_decode_compiles(one_chip, ppb, ns):
    fn = functools.partial(paged_attention_kernel, scale=D ** -0.5,
                           interpret=False, pages_per_block=ppb,
                           num_splits=ns)
    text = _compiled_text(
        fn, one_chip, ((B, N_KV, G, D), jnp.bfloat16), _pool(), _pool(),
        ((B, MAX_PAGES), jnp.int32), ((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_prefill_compiles(one_chip):
    fn = functools.partial(paged_prefill_kernel, scale=D ** -0.5,
                           interpret=False, pages_per_block=2, num_splits=1,
                           q_block=32)
    text = _compiled_text(
        fn, one_chip, ((B, 512, N_KV * G, D), jnp.bfloat16), _pool(),
        _pool(), ((B, MAX_PAGES), jnp.int32), ((B,), jnp.int32),
        ((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_paged_prefill_op_keeps_its_kernel_name(one_chip):
    """The chunked prefill op the engine calls compiles to a kernel named
    ``paged_prefill``, which ``prefill_attn_roofline`` reads."""
    fn = functools.partial(ops.paged_prefill, interpret=False)
    text = _compiled_text(
        fn, one_chip, ((B, 512, N_KV * G, D), jnp.bfloat16), _pool(),
        _pool(), ((B, MAX_PAGES), jnp.int32), ((B,), jnp.int32),
        ((B,), jnp.int32))
    assert "paged_prefill" in kernel_names(text)


def test_combine_compiles(one_chip):
    fn = functools.partial(combine_partials_pallas, dtype=jnp.bfloat16,
                           interpret=False)
    S = 4
    text = _compiled_text(
        fn, one_chip, ((B, N_KV, S, G), jnp.float32),
        ((B, N_KV, S, G), jnp.float32), ((B, N_KV, S, G, D), jnp.float32))
    assert "tpu_custom_call" in text


def test_flex_prefill_causal_padding_compiles(one_chip):
    """The monolithic prefill the engine runs: causal + padding mask."""
    def fn(q, k, v, lens):
        return prefill_attention(q, k, v, lens=lens, impl="pallas",
                                 interpret=False)
    S = 1536
    text = _compiled_text(
        fn, one_chip, ((B, S, N_KV * G, D), jnp.bfloat16),
        ((B, S, N_KV, D), jnp.bfloat16), ((B, S, N_KV, D), jnp.bfloat16),
        ((B,), jnp.int32))
    assert "tpu_custom_call" in text


def test_engine_decode_step_compiles(one_chip):
    """One whole jitted Engine decode step (depth cut to 2 layers)."""
    cfg = get_config("granite-8b").replace(n_layers=2)
    params = build_model(cfg).abstract_params(jnp.bfloat16)
    eng = Engine(cfg, params, impl="pallas", interpret=False,
                 dtype=jnp.bfloat16, max_slots=B, max_seq_len=2048,
                 pool_tokens=4096)
    st = dict(eng.state)
    st["tables"] = eng._tables_array(decode=True)
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (params, jnp.zeros((B,), jnp.int32), st))
    text = eng._jit_decode.lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    # the name decode_attn_roofline reads the decode kernel's time by
    assert "paged_attention" in kernel_names(text)


def test_engine_chunk_step_compiles(one_chip):
    """The engine's jitted chunk-prefill program at its fixed
    ``(max_slots, prefill_chunk)`` shape (depth cut to 2 layers)."""
    cfg = get_config("granite-8b").replace(n_layers=2)
    params = build_model(cfg).abstract_params(jnp.bfloat16)
    C = 512
    eng = Engine(cfg, params, impl="pallas", interpret=False,
                 dtype=jnp.bfloat16, max_slots=B, max_seq_len=2048,
                 pool_tokens=4096, prefill_chunk=C)
    st = {"pos": eng.state["pos"], "k_pages": eng.state["k_pages"],
          "v_pages": eng.state["v_pages"], "tables": eng._tables_array()}
    row = jnp.zeros((B,), jnp.int32)
    args = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        (params, jnp.zeros((B, C), jnp.int32), st, row, row))
    text = eng._jit_chunk.lower(*args, None).compile().as_text()
    # the name prefill_attn_roofline reads the prefill kernel's time by,
    # now inside the one program that holds the whole chunk step
    assert "paged_prefill" in kernel_names(text)
