"""Multi-device distributed tests (8 fake CPU devices, subprocess).

shard_map features can't run on the main process's single device, so each
test launches a subprocess with XLA_FLAGS=--xla_force_host_platform_
device_count=8 and asserts numerical equivalence against the single-device
reference: EP MoE dispatch, ring attention, kvp flash-decoding, and the
weight-stationary decode plan.
"""

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(body: str):
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys; sys.path.insert(0, %r)
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.sharding import use_mesh, DEFAULT_RULES
        from repro.launch.mesh import make_mesh
    """) % os.path.abspath(SRC) + textwrap.dedent(body)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


@pytest.mark.slow
def test_ep_moe_matches_reference():
    run_sub("""
        from repro.configs import get_smoke
        from repro.models import moe
        from repro.models.api import build_model
        from repro.distributed import ep
        mesh = make_mesh((4,2), ("data","model"))
        cfg = get_smoke('olmoe-1b-7b').replace(moe_capacity=0.0)
        rules = DEFAULT_RULES.extend(batch=("data",))
        m = build_model(cfg)
        params = m.init_params(jax.random.PRNGKey(0))
        p = jax.tree_util.tree_map(lambda a: a[0],
                                   params['groups']['0A'])['moe']
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, cfg.d_model))
        ref, _ = moe.apply_moe(p, x, cfg)
        with use_mesh(mesh, rules):
            out, _ = jax.jit(lambda p, x: ep.apply_moe_ep(p, x, cfg))(p, x)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=2e-5, atol=2e-5)
    """)


@pytest.mark.slow
def test_ring_attention_matches_dense():
    run_sub("""
        from repro.core.attention import prefill_attention
        mesh = make_mesh((2,4), ("data","model"))
        rules = DEFAULT_RULES.extend(batch=("data",), seq=("model",),
                                     heads=None, kv_heads=None)
        rng = jax.random.PRNGKey(0)
        for (B,S,H,Hkv,D,window,lens) in [(2,64,8,2,16,0,None),
                                          (2,128,4,4,32,30,None),
                                          (2,64,8,4,16,0,[50,33])]:
            ks = jax.random.split(rng,4); rng = ks[0]
            q = jax.random.normal(ks[1],(B,S,H,D))
            k = jax.random.normal(ks[2],(B,S,Hkv,D))
            v = jax.random.normal(ks[3],(B,S,Hkv,D))
            l = jnp.asarray(lens,jnp.int32) if lens else None
            ref = prefill_attention(q,k,v,window=window,lens=l,impl='jnp')
            with use_mesh(mesh, rules):
                out = jax.jit(lambda q,k,v: prefill_attention(
                    q,k,v,window=window,lens=l,impl='ring'))(q,k,v)
            np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                       rtol=3e-5, atol=3e-5)
    """)


@pytest.mark.slow
def test_kvp_flash_decoding_matches_local():
    run_sub("""
        from repro.core.attention import decode_attention
        from repro.distributed.collectives import decode_attention_sharded
        mesh = make_mesh((2,4), ("data","model"))
        B, Hkv, G, D, ps, pps, n_sh = 2, 2, 4, 16, 4, 8, 4
        num_pages = B * pps
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q4 = jax.random.normal(ks[0], (B, Hkv, G, D))
        kp = jax.random.normal(ks[1], (num_pages, Hkv, ps, D))
        vp = jax.random.normal(ks[2], (num_pages, Hkv, ps, D))
        lens = jnp.asarray([29, 17], jnp.int32)
        logical = jnp.arange(B*pps, dtype=jnp.int32).reshape(B, pps)
        ref = decode_attention(q4.reshape(B, Hkv*G, D), kp, vp, logical,
                               lens, impl='ref').reshape(B, Hkv, G, D)
        # kvp layout: batch over "data" (1 seq/shard), pages striped over
        # "model": shard (d, s) holds seq d's logical pages j*4+s in local
        # slot j. Physical pool reordered to that P(("data","model")) split.
        order = [d*pps + j*n_sh + s
                 for d in range(B) for s in range(n_sh)
                 for j in range(pps//n_sh)]
        kp2 = kp[jnp.asarray(order)]
        vp2 = vp[jnp.asarray(order)]
        local_tables = jnp.tile(
            jnp.arange(pps//n_sh, dtype=jnp.int32)[None, None],
            (B, n_sh, 1))
        from repro.distributed.sharding import use_mesh, DEFAULT_RULES
        rules = DEFAULT_RULES.extend(batch=("data",))
        with use_mesh(mesh, rules):
            out = jax.jit(lambda q4, kp, vp, t, l: decode_attention_sharded(
                q4, kp, vp, t, l, scheme='kvp', batch_axes=("data",),
                impl='ref'))(q4, kp2, vp2, local_tables, lens)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(out),
                                   rtol=2e-5, atol=2e-5)
    """)


@pytest.mark.slow
def test_serve_step_lowers_on_8dev_mesh():
    run_sub("""
        from repro.configs import get_smoke
        from repro.configs.base import RunConfig
        from repro.launch.steps import build_step, plan_for
        mesh = make_mesh((2,4), ("data","model"))
        cfg = get_smoke('granite-8b')
        run = RunConfig(model=cfg, seq_len=64, global_batch=4, kind='decode')
        for ws in (False, True):
            plan = plan_for(run, mesh, ws_decode=ws)
            step, args, sh, model = build_step(run, plan, dtype=jnp.float32)
            names = list(args)
            with use_mesh(mesh, plan.rules):
                lowered = jax.jit(step, in_shardings=tuple(
                    sh[n] for n in names)).lower(*(args[n] for n in names))
            lowered.compile()
    """)
