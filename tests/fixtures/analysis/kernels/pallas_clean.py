"""Contract-respecting pallas_call idioms (fixture — parsed, never run).

Exercises the resolution paths the checker must handle without false
positives: module-constant dimension_semantics, grid_spec prefetch,
factory lambdas returning BlockSpecs, functools.partial-bound index maps,
list-concatenation in_specs, and vararg index maps absorbing prefetch.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DIM_SEMANTICS = ("parallel", "arbitrary")


def _kernel(q_ref, o_ref):
    o_ref[...] = q_ref[...]


def good_dim_semantics(q):
    return pl.pallas_call(
        _kernel,
        grid=(4, 4),
        in_specs=[pl.BlockSpec(q.shape, lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec(q.shape, lambda i, j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=DIM_SEMANTICS),
    )(q)


def _kv_map(i, j, tables, page=0):
    return (tables[i], j)


def good_prefetch(q, tables):
    # rank 2 + 1 prefetch = 3-arg maps; kv maps bound via partial,
    # in_specs built by list concatenation from a factory lambda
    whole = lambda arr: pl.BlockSpec(arr.shape, lambda i, j, t: (0, 0))
    kv_spec = lambda p: pl.BlockSpec(
        q.shape, functools.partial(_kv_map, page=p))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(4, 4),
        in_specs=[whole(q)] + [kv_spec(p) for p in range(2)],
        out_specs=pl.BlockSpec(q.shape, lambda i, j, t: (0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
    )(tables, q, q, q)


def good_vararg_maps(q, tables, lens):
    # *pref absorbs a trailing prefetch pack of unresolvable size
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(4, 4),
        in_specs=[pl.BlockSpec(q.shape, lambda i, j, *pref: (0, 0))],
        out_specs=pl.BlockSpec(q.shape, lambda i, j, *pref: (0, 0)),
    )
    return pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
    )(tables, lens, q)


def _split_partials_kernel(q_ref, m_ref, l_ref, acc_ref):
    acc_ref[...] = q_ref[...]


def good_partials(q):
    return pl.pallas_call(
        _split_partials_kernel,
        grid=(2,),
        in_specs=[pl.BlockSpec(q.shape, lambda s: (0, 0))],
        out_specs=[pl.BlockSpec(q.shape, lambda s: (0, 0))] * 3,
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
            jax.ShapeDtypeStruct(q.shape, jnp.float32),
        ],
    )(q)


def combine_partials_like(m, l, acc):
    # "combine" consumes partials and emits ONE output — must not be
    # held to the three-output partials contract
    return pl.pallas_call(
        _kernel,
        grid=(2,),
        in_specs=[pl.BlockSpec(m.shape, lambda s: (0, 0))],
        out_specs=pl.BlockSpec(m.shape, lambda s: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(m.shape, jnp.bfloat16),
    )(m)
