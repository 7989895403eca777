"""The Llama-style decoder block, as the Granite papers describe it
(arXiv:2405.04324 for the dense code models, the Granite 3.0 report for
the MoE ones):

    x = embed[tokens] * embedding_multiplier
    per layer:
        h = rmsnorm(x) ; q, k, v = h Wq, h Wk, h Wv ; rotary on q, k
        a = softmax(q k^T * attention_multiplier, causal) v   (grouped KV)
        x = x + residual_multiplier * a Wo
        h = rmsnorm(x)
        x = x + residual_multiplier * ffn(h)
          dense: (silu(h Wg) * h Wu) Wd
          MoE:   sum over the top-k experts of the renormalized softmax
                 router weight times that expert's SwiGLU
    logits = rmsnorm(x) E^T / logits_scaling

Every layer attends through the paged kernels.  The reference
(``reference``, ``forward``, ``logits``) uses nothing of the engine;
``engine_config`` and ``to_engine`` are the engine's side.
"""

from __future__ import annotations

import copy
from dataclasses import dataclass
from functools import partial
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from flops import BF16
from reference import BUCKET, HI, OUT_BLOCK, Q_BLOCK, mm, rms_norm, rope
from repro.configs.base import ModelConfig
from weights import INIT_STD, seed_key

__all__ = ["Shape", "shape", "tiny", "engine_config", "to_engine", "draw",
           "Arch", "reference", "forward", "logits"]


# ---------------------------------------------------------------------------
# sizes and the work they count
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Shape:
    """Useful work only: the matrix products of the active parameters,
    attention over the live context, and the key/value bytes that context
    occupies.  Padding rows, whole pages past the live length and
    recomputation are not counted."""

    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    experts: int = 0
    top_k: int = 0

    @property
    def attn_layers(self) -> int:
        return self.layers

    @property
    def active_matmul_params(self) -> int:
        """Weights one token multiplies through: attention projections,
        the MLP (or the router and its top-k experts), the unembedding."""
        hd = self.head_dim
        attn = self.d * hd * (2 * self.heads + 2 * self.kv_heads)
        if self.experts:
            ffn = self.d * self.experts + self.top_k * 3 * self.d * self.ff
        else:
            ffn = 3 * self.d * self.ff
        return self.layers * (attn + ffn) + self.d * self.vocab

    def kv_bytes_per_token_layer(self) -> int:
        return 2 * self.kv_heads * self.head_dim * BF16

    def decode_flops(self, ctx: int) -> float:
        """One decoded token that attends over ``ctx`` tokens."""
        return (2.0 * self.active_matmul_params
                + 4.0 * self.layers * self.heads * self.head_dim * ctx)

    def prefill_flops(self, start: int, n: int) -> float:
        """``n`` prompt tokens after ``start`` cached ones, causal."""
        pairs = n * start + n * (n + 1) / 2
        return (2.0 * self.active_matmul_params * n
                + 4.0 * self.layers * self.heads * self.head_dim * pairs)

    def decode_attn_cost(self, ctx: int) -> Tuple[float, float]:
        """(FLOPs, bytes) of one decode row's attention in one layer."""
        flops = 4.0 * self.heads * self.head_dim * ctx
        qo = 2 * self.heads * self.head_dim * BF16
        return flops, ctx * self.kv_bytes_per_token_layer() + qo

    def prefill_attn_cost(self, start: int, n: int) -> Tuple[float, float]:
        """(FLOPs, bytes) of one prefill row's attention in one layer."""
        pairs = n * start + n * (n + 1) / 2
        flops = 4.0 * self.heads * self.head_dim * pairs
        qo = 2 * n * self.heads * self.head_dim * BF16
        return flops, (start + n) * self.kv_bytes_per_token_layer() + qo


def _head_dim(m: Dict) -> int:
    return m.get("head_dim") or m["hidden_size"] // m["num_attention_heads"]


def shape(m: Dict) -> Shape:
    return Shape(d=m["hidden_size"], layers=m["num_hidden_layers"],
                 heads=m["num_attention_heads"],
                 kv_heads=m["num_key_value_heads"], head_dim=_head_dim(m),
                 ff=m["intermediate_size"], vocab=m["vocab_size"],
                 experts=m.get("num_local_experts", 0),
                 top_k=m.get("num_experts_per_tok", 0))


def tiny(m: Dict) -> Dict:
    """The model section at the CPU tests' size."""
    m = copy.deepcopy(m)
    m.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2,
             head_dim=32, intermediate_size=128, num_hidden_layers=2,
             vocab_size=256)
    if m.get("num_local_experts"):
        m.update(num_local_experts=4, num_experts_per_tok=2)
    if "attention_multiplier" in m:
        m["attention_multiplier"] = 32 ** -0.5
    return m


# ---------------------------------------------------------------------------
# the engine's side
# ---------------------------------------------------------------------------
def engine_config(config: Dict) -> ModelConfig:
    """The engine's ``ModelConfig`` for a configuration file.  The engine
    fixes some of the arithmetic (RMSNorm epsilon 1e-6, unit multipliers,
    attention scale 1/sqrt(head_dim), no biases), and the weights drawn
    here share the embedding with the output head; a file that states
    other values cannot be run as stated and is refused."""
    m = config["model"]
    hd = _head_dim(m)
    fixed = {"rms_norm_eps": 1e-6, "embedding_multiplier": 1.0,
             "residual_multiplier": 1.0, "logits_scaling": 1.0,
             "attention_multiplier": hd ** -0.5,
             "attention_bias": False, "mlp_bias": False}
    for k, v in fixed.items():
        if k in m and not np.isclose(m[k], v, rtol=1e-6, atol=0):
            raise ValueError(f"{config['name']}: the engine runs {k}={v}, "
                             f"the file states {m[k]}")
    if not m.get("tie_word_embeddings", False):
        raise ValueError(f"{config['name']}: this block ties the embedding "
                         f"to the output head; the file does not")
    experts = m.get("num_local_experts", 0)
    return ModelConfig(
        name=config["name"], family="moe" if experts else "dense",
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"],
        n_kv_heads=m["num_key_value_heads"], head_dim=hd,
        d_ff=m["intermediate_size"], vocab_size=m["vocab_size"],
        activation="silu", rope_theta=float(m["rope_theta"]),
        tie_embeddings=m["tie_word_embeddings"], n_experts=experts,
        top_k=m.get("num_experts_per_tok", 0),
        d_ff_expert=m["intermediate_size"] if experts else 0,
        moe_capacity=0.0, page_size=config["serving"]["page_size"])


def to_engine(w: Dict, s: Shape) -> Dict:
    """The engine's parameter tree over the same arrays (no copy): one
    scanned group of ``s.layers`` attention layers, tied embeddings."""
    L = w["layers"]
    group = {"ln1": {"scale": L["ln1"]}, "ln2": {"scale": L["ln2"]},
             "attn": {k: L[k] for k in ("wq", "wk", "wv", "wo")}}
    ffn = {k: L[k] for k in ("wg", "wu", "wd")}
    if s.experts:
        group["moe"] = dict(ffn, router=L["router"])
    else:
        group["mlp"] = ffn
    return {"embed": {"tok": w["embed"]}, "ln_f": {"scale": w["ln_f"]},
            "groups": {"0A": group}, "rem": {}}


# ---------------------------------------------------------------------------
# seeded weights
# ---------------------------------------------------------------------------
def layer_shapes(s: Shape) -> Dict[str, tuple]:
    """Shapes of one layer's leaves, by the reference's names."""
    d, H, K, D = s.d, s.heads, s.kv_heads, s.head_dim
    out = {"ln1": (d,), "wq": (d, H, D), "wk": (d, K, D), "wv": (d, K, D),
           "wo": (H, D, d), "ln2": (d,)}
    if s.experts:
        E = s.experts
        out.update(router=(d, E), wg=(E, d, s.ff), wu=(E, d, s.ff),
                   wd=(E, s.ff, d))
    else:
        out.update(wg=(d, s.ff), wu=(d, s.ff), wd=(s.ff, d))
    return out


@partial(jax.jit, static_argnums=(0, 2))
def _draw(s: Shape, key: jax.Array, dtype) -> Dict:
    shapes = layer_shapes(s)
    keys = jax.random.split(key, len(shapes) + 1)
    stacked = {}
    for k, (name, shape) in zip(keys[1:], sorted(shapes.items())):
        full = (s.layers,) + shape
        if name.startswith("ln"):
            stacked[name] = jnp.ones(full, dtype)
        else:
            stacked[name] = (jax.random.normal(k, full, jnp.float32)
                             * INIT_STD).astype(dtype)
    embed = (jax.random.normal(keys[0], (s.vocab, s.d), jnp.float32)
             * INIT_STD).astype(dtype)
    return {"embed": embed, "ln_f": jnp.ones((s.d,), dtype),
            "layers": stacked}


def draw(s: Shape, seed: int, dtype=jnp.bfloat16) -> Dict:
    """All weights of the model, stacked by layer, drawn from ``seed``."""
    return _draw(s, seed_key(seed), jnp.dtype(dtype))


# ---------------------------------------------------------------------------
# the plain float32 reference
# ---------------------------------------------------------------------------
@dataclass(frozen=True)
class Arch:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    experts: int
    top_k: int
    rope_theta: float
    eps: float
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float


def reference(m: Dict) -> Arch:
    hd = _head_dim(m)
    return Arch(d=m["hidden_size"], layers=m["num_hidden_layers"],
                heads=m["num_attention_heads"],
                kv_heads=m["num_key_value_heads"], head_dim=hd,
                experts=m.get("num_local_experts", 0),
                top_k=m.get("num_experts_per_tok", 0),
                rope_theta=float(m["rope_theta"]),
                eps=float(m["rms_norm_eps"]),
                embedding_multiplier=float(
                    m.get("embedding_multiplier", 1.0)),
                attention_multiplier=float(
                    m.get("attention_multiplier", hd ** -0.5)),
                residual_multiplier=float(m.get("residual_multiplier", 1.0)),
                logits_scaling=float(m.get("logits_scaling", 1.0)))


@partial(jax.jit, static_argnames=("a", "quant"))
def _layer(x, stacked, i, *, a: Arch, quant: bool):
    """One layer over the whole (padded) sequence ``x`` (T, d) f32."""
    w = {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
         for k, v in stacked.items()}
    T = x.shape[0]
    pos = jnp.arange(T)
    h = rms_norm(x, w["ln1"], a.eps)
    q = rope(mm("td,dhk->thk", h, w["wq"], quant), pos, a.rope_theta)
    k = rope(mm("td,dhk->thk", h, w["wk"], quant), pos, a.rope_theta)
    v = mm("td,dhk->thk", h, w["wv"], quant)
    G = a.heads // a.kv_heads
    k = jnp.repeat(k, G, axis=1)
    v = jnp.repeat(v, G, axis=1)

    def block(qb_start):
        qb = jax.lax.dynamic_slice_in_dim(q, qb_start, Q_BLOCK, 0)
        s = mm("qhk,thk->hqt", qb, k, quant, a_rows=False)
        s = s * a.attention_multiplier
        rows = qb_start + jnp.arange(Q_BLOCK)
        s = jnp.where(rows[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("hqt,thk->qhk", p, v, quant, a_rows=False)

    o = jax.lax.map(block, jnp.arange(0, T, Q_BLOCK))
    o = o.reshape(T, a.heads, a.head_dim)
    x = x + a.residual_multiplier * mm("thk,hkd->td", o.reshape(
        T, a.heads, a.head_dim), w["wo"], quant, a_rows=False)
    h = rms_norm(x, w["ln2"], a.eps)
    if a.experts:
        probs = jax.nn.softmax(mm("td,de->te", h, w["router"], quant), -1)
        top, idx = jax.lax.top_k(probs, a.top_k)
        top = top / jnp.sum(top, axis=-1, keepdims=True)
        gates = jnp.zeros_like(probs).at[
            jnp.arange(T)[:, None], idx].set(top)
        g = mm("td,edf->tef", h, w["wg"], quant)
        u = mm("td,edf->tef", h, w["wu"], quant)
        y = mm("tef,efd->ted", jax.nn.silu(g) * u, w["wd"], quant,
               a_rows=False)
        f = jnp.einsum("te,ted->td", gates, y, precision=HI)
    else:
        g = mm("td,df->tf", h, w["wg"], quant)
        u = mm("td,df->tf", h, w["wu"], quant)
        f = mm("tf,fd->td", jax.nn.silu(g) * u, w["wd"], quant)
    return x + a.residual_multiplier * f


@partial(jax.jit, static_argnames=("a", "quant"))
def _rows(x, start, ln_f, embed, *, a: Arch, quant: bool):
    """Logits (f32) at the ``OUT_BLOCK`` positions from ``start``."""
    xs = jax.lax.dynamic_slice_in_dim(x, start, OUT_BLOCK, 0)
    h = rms_norm(xs, ln_f, a.eps)
    return mm("td,vd->tv", h, embed, quant) / a.logits_scaling


def forward(a: Arch, w: Dict, tokens: List[int], first: int,
            quant: bool = False) -> jax.Array:
    """Final hidden states of a teacher-forced pass over ``tokens``,
    padded to a multiple of ``BUCKET`` that holds ``OUT_BLOCK`` positions
    from ``first``."""
    n = max(len(tokens), first + OUT_BLOCK)
    ids = np.zeros((-(-n // BUCKET) * BUCKET,), np.int32)
    ids[:len(tokens)] = tokens
    x = w["embed"][jnp.asarray(ids)].astype(jnp.float32)
    x = x * a.embedding_multiplier
    for i in range(a.layers):
        x = _layer(x, w["layers"], i, a=a, quant=quant)
    return x


def logits(a: Arch, w: Dict, x: jax.Array, first: int,
           quant: bool = False) -> jax.Array:
    """(OUT_BLOCK, vocab) logits at positions ``first ..`` of ``x``."""
    return _rows(x, first, w["ln_f"], w["embed"], a=a, quant=quant)
