"""Logical-axis sharding rules (MaxText-style).

Model code annotates tensors with *logical* axis names ("batch", "heads",
"embed", ...).  A rule table maps each logical axis to zero or more physical
mesh axes.  Outside a mesh context every annotation is a no-op, so the same
model code runs on a laptop CPU and on a 512-chip dry-run unchanged.

Example
-------
    rules = AxisRules({"batch": ("pod", "data"), "heads": "model"})
    with use_mesh(mesh, rules):
        x = logical_shard(x, "batch", None, "embed")
"""

from __future__ import annotations

import contextlib
import threading
from dataclasses import dataclass, field
from typing import Any, Mapping, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


PhysAxes = Union[None, str, Tuple[str, ...]]


def _norm(v: PhysAxes) -> Optional[Tuple[str, ...]]:
    if v is None:
        return None
    if isinstance(v, str):
        return (v,)
    return tuple(v)


@dataclass(frozen=True)
class AxisRules:
    """Mapping from logical axis name to physical mesh axes."""

    table: Mapping[str, PhysAxes] = field(default_factory=dict)

    def physical(self, logical: Optional[str]) -> Optional[Tuple[str, ...]]:
        if logical is None:
            return None
        return _norm(self.table.get(logical))

    def extend(self, **overrides: PhysAxes) -> "AxisRules":
        t = dict(self.table)
        t.update(overrides)
        return AxisRules(t)


# Rules for the production (pod, data, model) mesh.  Configs may override.
DEFAULT_RULES = AxisRules(
    {
        "batch": ("pod", "data"),
        "embed": None,        # overridden to ("data",) for FSDP on big models
        "heads": ("model",),
        "kv_heads": None,     # set to ("model",) when kv_heads % model == 0
        "mlp": ("model",),
        "experts": ("model",),
        # NOTE: the GSPMD-annotated MoE dispatch leaves the (E, C, d)
        # expert buffers with no batch-sharded dim — every data shard
        # redundantly computes all experts (useful_frac caught the 16x
        # waste). Annotating C with the batch axes makes GSPMD lower the
        # dispatch gather as a one-hot matmul (measured: 4x memory, 100x
        # FLOPs — worse). The real fix is the explicit shard_map EP path
        # (ep_moe in distributed/ep.py), hillclimbed in EXPERIMENTS §Perf.
        "vocab": ("model",),
        "kv_pages": ("pod", "data"),
        "seq": None,        # ("model",) under the sequence-parallel train plan
        "attn_seq": None,   # q/k/v seq dim; ("model",) under the ring plan
        "act_embed": None,  # activations' model dim (distinct from weight "embed")
        "layers": None,
        "state": ("model",),  # recurrent state heads (SSM/RG-LRU)
        "frames": None,
    }
)


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: AxisRules = DEFAULT_RULES


_CTX = _Ctx()


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Optional[AxisRules] = None):
    """Activate a mesh + rule table for logical_shard annotations."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh = mesh
    if rules is not None:
        _CTX.rules = rules
    try:
        if mesh is not None:
            with mesh:
                yield
        else:
            yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def current_rules() -> AxisRules:
    return _CTX.rules


def _mesh_axis_sizes(mesh: Mesh) -> Mapping[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def axis_size(name: str) -> int:
    """Static size of a named mesh axis, usable inside shard_map bodies.

    Callers need a *static* int (ring permutation lists, mixed-radix index
    math), so resolve from the active mesh context first and otherwise
    ask ``jax.lax.axis_size``.
    """
    mesh = current_mesh()
    if mesh is not None and name in mesh.axis_names:
        return _mesh_axis_sizes(mesh)[name]
    return jax.lax.axis_size(name)


def logical_spec(
    logical_axes: Sequence[Optional[str]],
    rules: Optional[AxisRules] = None,
    mesh: Optional[Mesh] = None,
    shape: Optional[Sequence[int]] = None,
) -> P:
    """Resolve logical axes to a PartitionSpec.

    If ``shape`` is given, any mapping whose mesh-axis product does not divide
    the dimension is dropped (replicated) — this keeps small smoke configs
    valid under production rules.
    """
    rules = rules or current_rules()
    mesh = mesh or current_mesh()
    sizes = _mesh_axis_sizes(mesh) if mesh is not None else {}
    out = []
    used: set = set()
    for i, ax in enumerate(logical_axes):
        phys = rules.physical(ax)
        if phys is not None and mesh is not None:
            # drop mesh axes the current mesh doesn't have (e.g. "pod" on
            # the single-pod mesh) so one rule table serves every mesh
            phys = tuple(p for p in phys if p in sizes) or None
        if phys is not None and used.intersection(phys):
            # a mesh axis can shard at most one dim; first logical axis wins
            phys = None
        if phys is not None and shape is not None and mesh is not None:
            total = 1
            for p in phys:
                total *= sizes.get(p, 1)
            if shape[i] % total != 0:
                phys = None
        if phys is None:
            out.append(None)
        elif len(phys) == 1:
            used.update(phys)
            out.append(phys[0])
        else:
            used.update(phys)
            out.append(tuple(phys))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def logical_sharding(
    logical_axes: Sequence[Optional[str]],
    rules: Optional[AxisRules] = None,
    mesh: Optional[Mesh] = None,
    shape: Optional[Sequence[int]] = None,
) -> Optional[NamedSharding]:
    mesh = mesh or current_mesh()
    if mesh is None:
        return None
    return NamedSharding(mesh, logical_spec(logical_axes, rules, mesh, shape))


def logical_shard(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """Annotate ``x`` with a sharding constraint; no-op outside a mesh."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = logical_spec(logical_axes, current_rules(), mesh, x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def make_param_shardings(mesh: Mesh, rules: AxisRules, axes_tree, shapes_tree=None):
    """Map a pytree of logical-axis tuples to NamedShardings.

    ``shapes_tree`` (optional, of ShapeDtypeStruct/arrays) enables the
    divisibility fallback per leaf.
    """
    if shapes_tree is None:
        return jax.tree_util.tree_map(
            lambda axes: NamedSharding(mesh, logical_spec(axes, rules, mesh)),
            axes_tree,
            is_leaf=lambda x: isinstance(x, tuple),
        )
    return jax.tree_util.tree_map(
        lambda axes, s: NamedSharding(
            mesh, logical_spec(axes, rules, mesh, shape=s.shape)
        ),
        axes_tree,
        shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple),
    )
