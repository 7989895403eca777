"""Whisper-style encoder-decoder (arXiv:2212.04356).

The mel-spectrogram + conv frontend is STUBBED (harness carve-out):
``input_specs`` provides (B, n_frames, d_model) frame embeddings.  The
transformer itself is real: a bidirectional encoder and a causal decoder
whose every layer carries self-attention (paged KV cache at decode) +
cross-attention over encoder output (fixed-length KV, computed once at
prefill — the "fixed pages" case of the paper's allocator) + MLP.

Sinusoidal positions (no RoPE), LayerNorm, GELU (ungated).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.models import attention as attn
from repro.models import layers, spec as pspec
from repro.models.spec import ParamSpec


def _enc_layer_spec(cfg: ModelConfig) -> Dict:
    return {"ln1": layers.norm_spec(cfg), "attn": attn.attn_spec(cfg),
            "ln2": layers.norm_spec(cfg), "mlp": layers.mlp_spec(cfg)}


def _dec_layer_spec(cfg: ModelConfig) -> Dict:
    return {"ln1": layers.norm_spec(cfg), "self_attn": attn.attn_spec(cfg),
            "lnx": layers.norm_spec(cfg), "cross_attn": attn.attn_spec(cfg),
            "ln2": layers.norm_spec(cfg), "mlp": layers.mlp_spec(cfg)}


class EncDecModel:
    def __init__(self, cfg: ModelConfig):
        assert cfg.activation == "gelu_ungated", "whisper uses ungated GELU"
        self.cfg = cfg
        self.n_attn_layers = cfg.n_layers  # decoder self-attn layers
        self.window = 0

    def param_spec(self) -> Dict:
        cfg = self.cfg
        return {
            "embed": layers.embed_spec(cfg),
            "enc": pspec.stack_specs(_enc_layer_spec(cfg),
                                     cfg.n_encoder_layers, "layers"),
            "dec": pspec.stack_specs(_dec_layer_spec(cfg), cfg.n_layers,
                                     "layers"),
            "ln_enc": layers.norm_spec(cfg),
            "ln_f": layers.norm_spec(cfg),
        }

    def init_params(self, rng, dtype=jnp.float32):
        return pspec.materialize(self.param_spec(), rng, dtype)

    def param_axes(self):
        return pspec.axes_tree(self.param_spec())

    def abstract_params(self, dtype=jnp.float32):
        return pspec.abstract(self.param_spec(), dtype)

    # ------------------------------------------------------------------
    def encode(self, params: Dict, frames: jax.Array,
               impl: str = "jnp") -> jax.Array:
        """frames: (B, F, d) stubbed conv-frontend output → (B, F, d)."""
        cfg = self.cfg
        F = frames.shape[1]
        x = frames + layers.sinusoidal_positions(F, cfg.d_model)[None]
        x = x.astype(frames.dtype)

        def body(x, p):
            h = layers.apply_norm(p["ln1"], x)
            x = x + attn.attn_train(p["attn"], h, cfg, causal=False, impl=impl)
            x = x + layers.apply_mlp(p["mlp"],
                                     layers.apply_norm(p["ln2"], x), cfg)
            return x, None

        x, _ = jax.lax.scan(body, x, params["enc"],
                            unroll=cfg.scan_unroll or 1)
        return layers.apply_norm(params["ln_enc"], x)

    def forward(self, params: Dict, tokens: jax.Array,
                extra: Optional[Dict] = None, impl: str = "jnp") -> jax.Array:
        """Teacher-forced decode over (B, S) tokens with (B, F, d) frames."""
        cfg = self.cfg
        B, S = tokens.shape
        enc = self.encode(params, extra["frames"], impl)
        x = layers.embed_tokens(params["embed"], tokens)
        x = x + layers.sinusoidal_positions(S, cfg.d_model)[None].astype(x.dtype)

        def body(x, p):
            h = layers.apply_norm(p["ln1"], x)
            x = x + attn.attn_train(p["self_attn"], h, cfg, impl=impl)
            h = layers.apply_norm(p["lnx"], x)
            ck, cv = attn.cross_kv(p["cross_attn"], enc)
            x = x + attn.cross_attn(p["cross_attn"], h, ck, cv, cfg)
            x = x + layers.apply_mlp(p["mlp"],
                                     layers.apply_norm(p["ln2"], x), cfg)
            return x, None

        x, _ = jax.lax.scan(body, x, params["dec"],
                            unroll=cfg.scan_unroll or 1)
        x = layers.apply_norm(params["ln_f"], x)
        return layers.unembed(params["embed"], x, cfg)

    def loss_fn(self, params: Dict, batch: Dict, impl: str = "jnp"):
        from repro.models.transformer import _xent
        logits = self.forward(params, batch["inputs"],
                              {"frames": batch["frames"]}, impl)
        loss = _xent(logits, batch["targets"], batch.get("mask"))
        return loss, {"ce": loss, "aux": jnp.float32(0.0)}

    # ------------------------------------------------------------------
    def init_decode_state(self, run: RunConfig, dtype=jnp.float32,
                          n_kv_shards: int = 1, abstract: bool = False
                          ) -> Dict:
        cfg = self.cfg
        B = run.global_batch
        ps = cfg.page_size
        pages_per_seq = -(-run.pages_per_seq // n_kv_shards) * n_kv_shards
        num_pages = B * pages_per_seq
        Hkv, hd = cfg.n_kv_heads, cfg.resolved_head_dim

        def arr(shape, dt):
            if abstract:
                return jax.ShapeDtypeStruct(shape, dt)
            return jnp.zeros(shape, dt)

        pool = (cfg.n_layers, num_pages, Hkv, ps, hd)
        pool_dt = jnp.int8 if cfg.kv_dtype == "int8" else dtype
        return {
            "pos": arr((B,), jnp.int32),
            "k_pages": arr(pool, pool_dt),
            "v_pages": arr(pool, pool_dt),
            "tables": arr((B, n_kv_shards, pages_per_seq // n_kv_shards),
                          jnp.int32),
            "cross_k": arr((cfg.n_layers, B, cfg.n_audio_frames, Hkv, hd),
                           dtype),
            "cross_v": arr((cfg.n_layers, B, cfg.n_audio_frames, Hkv, hd),
                           dtype),
        }

    def prefill(self, params: Dict, tokens: jax.Array, state: Dict,
                lens: Optional[jax.Array] = None,
                extra: Optional[Dict] = None, impl: str = "jnp",
                attn_ctx: Optional[Dict] = None) -> Tuple[jax.Array, Dict]:
        cfg = self.cfg
        B, S = tokens.shape
        lens = lens if lens is not None else jnp.full((B,), S, jnp.int32)
        enc = self.encode(params, extra["frames"], impl)
        x = layers.embed_tokens(params["embed"], tokens)
        x = x + layers.sinusoidal_positions(S, cfg.d_model)[None].astype(x.dtype)

        st = dict(state)
        new_k, new_v, new_ck, new_cv = [], [], [], []
        for li in range(cfg.n_layers):
            p = jax.tree_util.tree_map(lambda a: a[li], params["dec"])
            h = layers.apply_norm(p["ln1"], x)
            o, kp, vp = attn.attn_prefill(
                p["self_attn"], h, cfg, st["k_pages"][li], st["v_pages"][li],
                st["tables"], lens, impl=impl)
            new_k.append(kp)
            new_v.append(vp)
            x = x + o
            h = layers.apply_norm(p["lnx"], x)
            ck, cv = attn.cross_kv(p["cross_attn"], enc)
            new_ck.append(ck)
            new_cv.append(cv)
            x = x + attn.cross_attn(p["cross_attn"], h, ck, cv, cfg)
            x = x + layers.apply_mlp(p["mlp"],
                                     layers.apply_norm(p["ln2"], x), cfg)

        st.update(k_pages=jnp.stack(new_k), v_pages=jnp.stack(new_v),
                  cross_k=jnp.stack(new_ck), cross_v=jnp.stack(new_cv),
                  pos=lens)
        x = layers.apply_norm(params["ln_f"], x)
        last = jnp.take_along_axis(
            x, jnp.maximum(lens - 1, 0)[:, None, None].astype(jnp.int32),
            axis=1)[:, 0]
        return layers.unembed(params["embed"], last, cfg), st

    def prefill_chunk(self, params: Dict, tokens: jax.Array, state: Dict,
                      q_start: jax.Array, q_lens: jax.Array,
                      extra: Optional[Dict] = None, impl: str = "jnp",
                      interpret: Optional[bool] = None,
                      pages_per_block: Optional[int] = None,
                      num_splits: Optional[int] = None,
                      combine_mode: Optional[str] = None,
                      backend: Optional[str] = None,
                      cross_mode: str = "full",
                      first_rows: Optional[jax.Array] = None
                      ) -> Tuple[jax.Array, Dict]:
        """Chunked decoder prefill (same contract as
        `TransformerModel.prefill_chunk`): the chunk's self-attention
        resumes from the cached prefix pages at ``q_start``.  The audio
        encoder and per-layer cross-attention K/V depend only on the
        frames, and the gate is **per row** (``cross_mode`` /
        ``first_rows``, from `attention.cross_gate`): "partial" encodes
        only the rows at chunk 0 — their frames gathered into a smaller
        encode batch, their fresh cross-K/V scattered into the cached
        ``state["cross_k"/"cross_v"]`` stack — so resume rows never pay
        the encoder again; "full" encodes every row, "reuse" none."""
        cfg = self.cfg
        C = tokens.shape[1]
        enc = None
        if cross_mode == "full":
            enc = self.encode(params, extra["frames"], impl)
        elif cross_mode == "partial":
            enc = self.encode(params, extra["frames"][first_rows], impl)
        pos = (q_start[:, None].astype(jnp.int32)
               + jnp.arange(C, dtype=jnp.int32)[None])
        x = layers.embed_tokens(params["embed"], tokens)
        x = x + layers.sinusoidal_at(pos, cfg.d_model).astype(x.dtype)

        st = dict(state)
        new_k, new_v, new_ck, new_cv = [], [], [], []
        for li in range(cfg.n_layers):
            p = jax.tree_util.tree_map(lambda a: a[li], params["dec"])
            h = layers.apply_norm(p["ln1"], x)
            o, kp, vp = attn.attn_prefill_chunked(
                p["self_attn"], h, cfg, st["k_pages"][li], st["v_pages"][li],
                st["tables"], q_start, q_lens, impl=impl,
                interpret=interpret, pages_per_block=pages_per_block,
                num_splits=num_splits, combine_mode=combine_mode,
                backend=backend)
            new_k.append(kp)
            new_v.append(vp)
            x = x + o
            h = layers.apply_norm(p["lnx"], x)
            if cross_mode == "reuse":
                ck, cv = state["cross_k"][li], state["cross_v"][li]
            elif cross_mode == "partial":
                # fresh cross-K/V for first-chunk rows only, scattered
                # into the cached stack; resume rows are untouched
                ck_new, cv_new = attn.cross_kv(p["cross_attn"], enc)
                ck = state["cross_k"][li].at[first_rows].set(ck_new)
                cv = state["cross_v"][li].at[first_rows].set(cv_new)
            else:
                ck, cv = attn.cross_kv(p["cross_attn"], enc)
            new_ck.append(ck)
            new_cv.append(cv)
            x = x + attn.cross_attn(p["cross_attn"], h, ck, cv, cfg)
            x = x + layers.apply_mlp(p["mlp"],
                                     layers.apply_norm(p["ln2"], x), cfg)

        st.update(k_pages=jnp.stack(new_k), v_pages=jnp.stack(new_v),
                  cross_k=jnp.stack(new_ck), cross_v=jnp.stack(new_cv),
                  pos=q_start + q_lens)
        x = layers.apply_norm(params["ln_f"], x)
        last = jnp.take_along_axis(
            x, jnp.maximum(q_lens - 1, 0)[:, None, None].astype(jnp.int32),
            axis=1)[:, 0]
        return layers.unembed(params["embed"], last, cfg), st

    def decode_step(self, params: Dict, tokens: jax.Array, state: Dict,
                    impl: str = "ref", attn_ctx: Optional[Dict] = None,
                    interpret: Optional[bool] = None,
                    pages_per_block: Optional[int] = None,
                    num_splits: Optional[int] = None,
                    combine_mode: Optional[str] = None,
                    backend: Optional[str] = None
                    ) -> Tuple[jax.Array, Dict]:
        cfg = self.cfg
        B = tokens.shape[0]
        pos = state["pos"]
        x = layers.embed_tokens(params["embed"], tokens)
        # closed-form sinusoidal position (decode positions may exceed
        # whisper's native 448 in the assigned decode_32k shape)
        x = x + layers.sinusoidal_at(pos, cfg.d_model).astype(x.dtype)
        tables = state["tables"]

        def body(x, xs):
            p, kp, vp, ck, cv = (xs["p"], xs["kp"], xs["vp"], xs["ck"],
                                 xs["cv"])
            h = layers.apply_norm(p["ln1"], x)
            o, kp, vp = attn.attn_decode(
                p["self_attn"], h, cfg, kp, vp, tables, pos, impl=impl,
                attn_ctx=attn_ctx, interpret=interpret,
                pages_per_block=pages_per_block, num_splits=num_splits,
                combine_mode=combine_mode, backend=backend)
            x = x + o
            h = layers.apply_norm(p["lnx"], x)
            x = x + attn.cross_attn(p["cross_attn"], h, ck, cv, cfg)
            x = x + layers.apply_mlp(p["mlp"],
                                     layers.apply_norm(p["ln2"], x), cfg)
            return x, {"kp": kp, "vp": vp}

        xs = {"p": params["dec"], "kp": state["k_pages"],
              "vp": state["v_pages"], "ck": state["cross_k"],
              "cv": state["cross_v"]}
        x, ys = jax.lax.scan(body, x, xs, unroll=cfg.scan_unroll or 1)

        st = dict(state, k_pages=ys["kp"], v_pages=ys["vp"], pos=pos + 1)
        x = layers.apply_norm(params["ln_f"], x)
        return layers.unembed(params["embed"], x, cfg), st
