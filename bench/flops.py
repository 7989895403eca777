"""Operations and bytes that the served work needs, from the cell's shapes.

These count useful work only: the matrix products of the active
parameters, attention over the live context, and the key/value bytes that
context occupies.  Padding rows, whole pages past the live length and
recomputation are not counted, so a share computed from them is at most
the work the device really did.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Tuple

BF16 = 2  # bytes per element of the served dtype


@dataclass(frozen=True)
class Shape:
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    experts: int = 0
    top_k: int = 0

    @classmethod
    def from_model(cls, m: Dict) -> "Shape":
        heads = m["num_attention_heads"]
        return cls(d=m["hidden_size"], layers=m["num_hidden_layers"],
                   heads=heads, kv_heads=m["num_key_value_heads"],
                   head_dim=m.get("head_dim") or m["hidden_size"] // heads,
                   ff=m["intermediate_size"], vocab=m["vocab_size"],
                   experts=m.get("num_local_experts", 0),
                   top_k=m.get("num_experts_per_tok", 0))

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


def decode_flops(s: Shape, ctx: int) -> float:
    """One decoded token that attends over ``ctx`` tokens."""
    return (2.0 * s.active_matmul_params
            + 4.0 * s.layers * s.heads * s.head_dim * ctx)


def prefill_flops(s: Shape, start: int, n: int) -> float:
    """``n`` prompt tokens after ``start`` cached ones, causal."""
    pairs = n * start + n * (n + 1) / 2
    return (2.0 * s.active_matmul_params * n
            + 4.0 * s.layers * s.heads * s.head_dim * pairs)


def decode_attn_cost(s: Shape, ctx: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode row's attention in one layer."""
    flops = 4.0 * s.heads * s.head_dim * ctx
    qo = 2 * s.heads * s.head_dim * BF16
    return flops, ctx * s.kv_bytes_per_token_layer() + qo


def prefill_attn_cost(s: Shape, start: int, n: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one prefill row's attention in one layer."""
    pairs = n * start + n * (n + 1) / 2
    flops = 4.0 * s.heads * s.head_dim * pairs
    qo = 2 * n * s.heads * s.head_dim * BF16
    return flops, (start + n) * s.kv_bytes_per_token_layer() + qo


def roofline_s(flops: float, nbytes: float, peak: Dict) -> float:
    """Least time the chip needs: the larger of the compute and the
    memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
