"""Declared launch contracts for every Pallas kernel in ``repro.kernels``.

This module is the *checkable* half of the kernel documentation: each
``pallas_call`` site in ``src/repro/kernels/`` (TPU and Triton decode,
prefill, combine, and the flex prefill kernel) declares its grid symbols,
operand shapes/dtypes, scalar-prefetch layout, output contract and the
value range of every prefetch table here — and ``replint``'s ``shapes``
rule abstractly interprets the site's BlockSpecs/index_maps against the
declaration for a set of concrete sample partitions.  Facts that used to
live in comments ("(B, n_kv, S, G) f32", "tables are clamped to
[0, num_pages-1]") are now data a checker consumes.

Deliberately **stdlib-only** (no jax): the checker loads this file by
path, so importing it must cost nothing.  ``decode_partition`` — the pure
integer partition law both backends share — lives here for the same
reason and is re-exported by ``paged_attention.py``.

Contract schema (one dict per site, keyed by the *enclosing function
name* of the ``pallas_call``)::

    "site_name": {
        "backend": "tpu" | "gpu",
        "grid": ("B", "n_kv", ...),      # axis symbols, for documentation
        "num_scalar_prefetch": int | symbol,
        "operands": [                     # call-operand order, prefetch first
            {"name": "tables3d",          # the site-local variable name
             "shape": ("B", "NB", "ppb"), # symbols/ints, or a sample key
             "dtype": "int32",            #   whose value is a shape tuple
             "repeat": "ppb",             # operand appears sample[repeat]×
             "value_range": (0, "NPm1")}, # int contents (inclusive bounds)
            ...],
        "outputs": [{"shape": (...), "dtype": "float32"}, ...],
        "partial_group": "decode-partials" | None,   # (m, l, acc) family
        "consumes": {"group": ..., "operands": (...)} | None,
        "samples": [ {symbol: int, ...}, ... ],      # concrete bindings
    }

Sample symbols must use the **site-local variable names** — the checker
evaluates the site's actual AST expressions (block shapes, grids,
index_maps, factory lambdas) under the sample binding, so the contract
only holds if the code and the declaration agree.  Exactly one sample per
contract sets ``"_parity": True``: members of a ``partial_group`` are
compared under their parity samples (TPU ≡ GPU partial-contract parity),
consumers (``consumes``) must ingest exactly the group's partial shapes
(the decode/prefill → combine handoff), and every partial must be f32.

To extend: add the contract dict alongside the new ``pallas_call``'s
function, reusing ``decode_partition`` for derived symbols, and give it a
parity sample if it emits or consumes split-K partials.  A site in
``src/repro/kernels/`` with no entry here is itself a finding.
"""

from __future__ import annotations

from typing import Dict, List, Tuple


def decode_partition(max_pages: int, pages_per_block: int = 1,
                     num_splits: int = 1) -> Tuple[int, int, int, int]:
    """Clamp knobs and derive the kernel's split/block partition.

    Returns ``(pages_per_block, n_blocks, num_splits, blocks_per_split)``.
    Single source of the partition law — the kernel grid, the auto-tuner
    (`ops.choose_decode_params`), the grid-step accounting
    (`decode_grid_steps`), the split-K oracle
    (`ref.paged_attention_partials_ref`) and the declared contracts below
    must all agree bit-for-bit on which pages land in which split.
    """
    max_pages = max(1, int(max_pages))
    ppb = max(1, min(int(pages_per_block), max_pages))
    n_blocks = -(-max_pages // ppb)
    ns = max(1, min(int(num_splits), n_blocks))
    bps = -(-n_blocks // ns)  # last split may cover padding blocks
    return ppb, n_blocks, ns, bps


# ---------------------------------------------------------------------------
# sample partitions — every boundary of the partition law gets a binding
# ---------------------------------------------------------------------------
# (max_pages, pages_per_block, num_splits, is_parity_sample)
_DECODE_CASES = [
    (4, 2, 2, True),    # even split — the canonical parity configuration
    (1, 1, 1, False),   # minimal: one page, one block, one split
    (7, 2, 3, False),   # ragged: blocks pad the page axis, splits pad blocks
    (5, 3, 8, False),   # num_splits clamped down to n_blocks
    (8, 4, 1, False),   # single split, wide block
]


def _decode_samples() -> List[Dict]:
    out = []
    for mp, pb, ns, parity in _DECODE_CASES:
        ppb, _, s, bps = decode_partition(mp, pb, ns)
        out.append({
            "B": 2, "n_kv": 2, "G": 4, "D": 8,
            "page_size": 4, "num_pages": 16, "NPm1": 15,
            "ppb": ppb, "S": s, "bps": bps, "NB": s * bps,
            "_parity": parity,
        })
    return out


def _prefill_samples() -> List[Dict]:
    # (max_pages, pages_per_block, num_splits, q_block, parity); the
    # parity sample uses q_block=1 so R == G and the q-block axis folds
    # onto the decode partial contract exactly.
    cases = [
        (4, 2, 2, 1, True),
        (7, 2, 3, 2, False),
        (1, 1, 1, 1, False),
        (8, 4, 2, 4, False),
    ]
    out = []
    for mp, pb, ns, q_block, parity in cases:
        ppb, _, s, bps = decode_partition(mp, pb, ns)
        g = 4
        out.append({
            "B": 2, "n_kv": 2, "G": g, "D": 8,
            "page_size": 4, "num_pages": 16, "NPm1": 15,
            "ppb": ppb, "S": s, "bps": bps, "NB": s * bps,
            # NQ deliberately differs from every other axis extent so a
            # fold along the wrong axis cannot alias into a clean check
            "NQ": 3, "R": q_block * g, "q_block": q_block,
            "_parity": parity,
        })
    return out


def _kv_pool(n_kv: str = "n_kv") -> Dict:
    return {"name": "k_pages",
            "shape": ("num_pages", n_kv, "page_size", "D"),
            "dtype": "float32"}


# ---------------------------------------------------------------------------
# the contract table — one entry per pallas_call site in src/repro/kernels/
# ---------------------------------------------------------------------------
_DECODE_OUTPUTS = [
    {"shape": ("B", "n_kv", "S", "G", 1), "dtype": "float32"},     # m
    {"shape": ("B", "n_kv", "S", "G", 1), "dtype": "float32"},     # l
    {"shape": ("B", "n_kv", "S", "G", "D"), "dtype": "float32"},   # acc
]
_PREFILL_OUTPUTS = [
    {"shape": ("B", "n_kv", "NQ", "S", "R", 1), "dtype": "float32"},
    {"shape": ("B", "n_kv", "NQ", "S", "R", 1), "dtype": "float32"},
    {"shape": ("B", "n_kv", "NQ", "S", "R", "D"), "dtype": "float32"},
]
_TABLES3D = {"name": "tables3d", "shape": ("B", "NB", "ppb"),
             "dtype": "int32", "value_range": (0, "NPm1")}

CONTRACTS: Dict[str, Dict] = {
    # -- TPU decode: scalar-prefetch block tables, ppb pages per grid step
    "paged_attention_partials": {
        "backend": "tpu",
        "grid": ("B", "n_kv", "S", "bps"),
        "num_scalar_prefetch": 2,
        "operands": [
            dict(_TABLES3D),
            {"name": "lens", "shape": ("B",), "dtype": "int32"},
            {"name": "q", "shape": ("B", "n_kv", "G", "D"),
             "dtype": "float32"},
            dict(_kv_pool(), repeat="ppb"),
            dict(_kv_pool(), name="v_pages", repeat="ppb"),
        ],
        "outputs": _DECODE_OUTPUTS,
        "partial_group": "decode-partials",
        "samples": _decode_samples(),
    },
    # -- Triton decode: whole-array pools, in-kernel table gathers
    "paged_attention_partials_gpu": {
        "backend": "gpu",
        "grid": ("B", "n_kv", "S"),
        "num_scalar_prefetch": 0,
        "operands": [
            dict(_TABLES3D),
            {"name": "lens", "shape": ("B",), "dtype": "int32"},
            {"name": "q", "shape": ("B", "n_kv", "G", "D"),
             "dtype": "float32"},
            dict(_kv_pool()),
            dict(_kv_pool(), name="v_pages"),
        ],
        "outputs": _DECODE_OUTPUTS,
        "partial_group": "decode-partials",
        "samples": _decode_samples(),
    },
    # -- TPU chunked prefill: decode grid + q-block axis, R = q_block·G rows
    "paged_prefill_partials": {
        "backend": "tpu",
        "grid": ("B", "n_kv", "NQ", "S", "bps"),
        "num_scalar_prefetch": 3,
        "operands": [
            dict(_TABLES3D),
            {"name": "kv_lens", "shape": ("B",), "dtype": "int32"},
            {"name": "q_start", "shape": ("B",), "dtype": "int32"},
            {"name": "qb5", "shape": ("B", "n_kv", "NQ", "R", "D"),
             "dtype": "float32"},
            dict(_kv_pool(), repeat="ppb"),
            dict(_kv_pool(), name="v_pages", repeat="ppb"),
        ],
        "outputs": _PREFILL_OUTPUTS,
        "partial_group": "prefill-partials",
        "samples": _prefill_samples(),
    },
    # -- Triton chunked prefill: identical partial contract to the TPU one
    "paged_prefill_partials_gpu": {
        "backend": "gpu",
        "grid": ("B", "n_kv", "NQ", "S"),
        "num_scalar_prefetch": 0,
        "operands": [
            dict(_TABLES3D),
            {"name": "kv_lens", "shape": ("B",), "dtype": "int32"},
            {"name": "q_start", "shape": ("B",), "dtype": "int32"},
            {"name": "qb5", "shape": ("B", "n_kv", "NQ", "R", "D"),
             "dtype": "float32"},
            dict(_kv_pool()),
            dict(_kv_pool(), name="v_pages"),
        ],
        "outputs": _PREFILL_OUTPUTS,
        "partial_group": "prefill-partials",
        "samples": _prefill_samples(),
    },
    # -- fused split-K combine: ingests exactly the decode partial contract
    "combine_partials_pallas": {
        "backend": "tpu",
        "grid": ("B", "Hkv"),
        "num_scalar_prefetch": 0,
        "operands": [
            {"name": "m", "shape": ("B", "Hkv", "S", "G", 1),
             "dtype": "float32"},
            {"name": "l", "shape": ("B", "Hkv", "S", "G", 1),
             "dtype": "float32"},
            {"name": "acc", "shape": ("B", "Hkv", "S", "G", "D"),
             "dtype": "float32"},
        ],
        "outputs": [{"shape": ("B", "Hkv", "G", "D"), "dtype": "float32"}],
        "partial_group": None,
        "consumes": {"group": "decode-partials",
                     "operands": ("m", "l", "acc")},
        "samples": [
            {"B": 2, "Hkv": 2, "S": 2, "G": 4, "D": 8,
             "dtype": "float32", "_parity": True},
            {"B": 1, "Hkv": 1, "S": 1, "G": 8, "D": 8,
             "dtype": "float32"},
            {"B": 3, "Hkv": 2, "S": 4, "G": 2, "D": 16,
             "dtype": "float32"},
        ],
    },
    # -- flex prefill: BlockMask-driven KV tile skipping (aux-free samples;
    #    aux scalar-prefetch operands ride behind *pref and are opaque to
    #    the shape checker)
    "flex_attention_kernel": {
        "backend": "tpu",
        "grid": ("B", "H", "nq", "max_kv"),
        "num_scalar_prefetch": "n_prefetch",
        "operands": [
            {"name": "kv_num_blocks", "shape": "kv_num_blocks_shape",
             "dtype": "int32"},
            {"name": "kv_indices", "shape": "kv_indices_shape",
             "dtype": "int32", "value_range": (0, "KBm1")},
            {"name": "is_full", "shape": "is_full_shape", "dtype": "int32"},
            {"name": "q", "shape": ("B", "H", "Q", "D"),
             "dtype": "float32"},
            {"name": "k", "shape": ("B", "Hkv", "K", "D"),
             "dtype": "float32"},
            {"name": "v", "shape": ("B", "Hkv", "K", "D"),
             "dtype": "float32"},
        ],
        "outputs": [{"shape": ("B", "H", "Q", "D"), "dtype": "float32"}],
        "partial_group": None,
        "samples": [
            # unbatched block mask (kv_indices rank 2)
            {"B": 2, "H": 4, "Q": 16, "D": 8, "Hkv": 2, "K": 16, "G": 2,
             "q_blk": 8, "kv_blk": 8, "nq": 2, "max_kv": 2,
             "n_prefetch": 3, "KBm1": 1,
             "kv_num_blocks_shape": (2,), "kv_indices_shape": (2, 2),
             "is_full_shape": (2, 2), "_parity": False},
            # batched block mask (kv_indices rank 3)
            {"B": 2, "H": 8, "Q": 32, "D": 8, "Hkv": 4, "K": 32, "G": 2,
             "q_blk": 8, "kv_blk": 16, "nq": 4, "max_kv": 2,
             "n_prefetch": 3, "KBm1": 1,
             "kv_num_blocks_shape": (2, 4), "kv_indices_shape": (2, 4, 2),
             "is_full_shape": (2, 4, 2)},
        ],
    },
}

# partial families: members must agree under their parity samples, and a
# group may fold onto another (the prefill q-block axis folds into the
# batch axis before the shared combine — `combine_prefill_partials`).
PARTIAL_GROUPS: Dict[str, Dict] = {
    "decode-partials": {},
    "prefill-partials": {"folds_into": "decode-partials", "fold_axis": 2},
}
