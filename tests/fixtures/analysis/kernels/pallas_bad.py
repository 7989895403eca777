"""Seeded pallas-contract violations (fixture — parsed, never executed)."""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BAD_DIM_SEMANTICS = ("parallel", "parallel", "arbitrary")  # len 3, grid rank 2


def _kernel(q_ref, o_ref):
    o_ref[...] = q_ref[...]


def bad_dim_semantics(q):
    # grid rank 2 but dimension_semantics has 3 entries
    return pl.pallas_call(
        _kernel,
        grid=(4, 4),
        in_specs=[pl.BlockSpec(q.shape, lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec(q.shape, lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=BAD_DIM_SEMANTICS),
    )(q)


def bad_index_map_arity(q):
    # grid rank 2, no scalar prefetch: index maps must take 2 params
    return pl.pallas_call(
        _kernel,
        grid=(4, 4),
        in_specs=[pl.BlockSpec(q.shape, lambda i, j, k: (0, 0))],
        out_specs=pl.BlockSpec(q.shape, lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
    )(q)


def bad_prefetch_arity(q, tables):
    # rank 2 + 1 scalar prefetch: maps need 3 params, these take 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(4, 4),
        in_specs=[pl.BlockSpec(q.shape, lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec(q.shape, lambda i, j: (0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
    )(tables, q)


def _partials_kernel(q_ref, m_ref, l_ref):
    m_ref[...] = q_ref[...]


def two_output_partials(q):
    # split-K partials must emit three (m, l, acc) outputs, not two
    return pl.pallas_call(
        _partials_kernel,
        grid=(2,),
        in_specs=[pl.BlockSpec(q.shape, lambda s: (0, 0))],
        out_specs=[pl.BlockSpec(q.shape, lambda s: (0, 0))] * 2,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
        ],
    )(q)


def halfprec_partials(q):
    # three outputs but the accumulator is bf16, not f32
    return pl.pallas_call(
        _partials_kernel,
        grid=(2,),
        in_specs=[pl.BlockSpec(q.shape, lambda s: (0, 0))],
        out_specs=[pl.BlockSpec(q.shape, lambda s: (0, 0))] * 3,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            jax.ShapeDtypeStruct(q.shape, jnp.bfloat16),
        ],
    )(q)
