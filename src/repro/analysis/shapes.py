"""shapes: abstract interpretation of every ``pallas_call`` launch.

The rule proves, at lint time and without running a kernel, that each
``pallas_call`` site in ``src/repro/kernels/`` agrees with its *declared
contract* (``repro/kernels/paged_attention/contracts.py``):

  * **rank** — every BlockSpec block shape has the operand's rank (the
    BlockSpec-vs-pool-array follow-up from the first replint PR);
  * **divisibility** — block dims divide the operand dims they tile;
  * **in-range indexing** — the ``index_map`` is evaluated symbolically
    for *every* grid point: grid axes become intervals ``[0, size-1]``,
    scalar-prefetch tables carry their declared value range (the
    ``_blocked_tables`` clamp, ``[0, num_pages-1]``), and interval
    arithmetic through ``s * bps + blk``-style expressions bounds every
    block index against the operand extent;
  * **partial dtypes** — split-K ``(m, l, acc)`` outputs must be f32;
  * **handoff + parity** — contracts in a ``partial_group`` must agree
    under their parity samples (TPU ≡ GPU), consumers (the combine
    kernel) must ingest exactly the group's shapes, and the prefill
    group must fold onto the decode group along its q-block axis.

Evaluation is concrete-per-sample: each contract carries sample bindings
(the partition-law boundary cases, derived through ``decode_partition``)
under which the site's actual AST — block shapes, grids, factory lambdas,
``functools.partial``-bound index_maps, list comprehensions over
``range(ppb)`` — is executed by a tiny abstract evaluator.  Only grid
indices and prefetch-table *contents* are intervals; everything else is
an integer, so the arithmetic is exact for the monotone expressions
index_maps use.

Fixtures (not importable) declare contracts inline as a literal::

    REPLINT_KERNEL_CONTRACTS = {"site_fn": {...}}    # ast.literal_eval'd
    REPLINT_PARTIAL_GROUPS = {"group": {...}}        # optional

A ``pallas_call`` under ``src/`` with no registry entry — or in any file
carrying an inline table but missing from it — is itself a finding, so
new kernels cannot dodge the checker.
"""

from __future__ import annotations

import ast
import importlib.util
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis.core import (FileContext, Finding, Project, call_name,
                                 dotted_name, kwarg, register, scope_env)

RULE = "shapes"
INLINE_TABLE = "REPLINT_KERNEL_CONTRACTS"
INLINE_GROUPS = "REPLINT_PARTIAL_GROUPS"
_REGISTRY_REL = Path("kernels") / "paged_attention" / "contracts.py"

_F32 = "float32"


# ---------------------------------------------------------------------------
# abstract values
# ---------------------------------------------------------------------------
class Interval:
    """Inclusive integer interval — the only abstract numeric value."""

    __slots__ = ("lo", "hi")

    def __init__(self, lo: int, hi: int):
        self.lo, self.hi = int(lo), int(hi)

    def __repr__(self):
        return f"[{self.lo}, {self.hi}]"


def _lo(v) -> int:
    return v.lo if isinstance(v, Interval) else int(v)


def _hi(v) -> int:
    return v.hi if isinstance(v, Interval) else int(v)


def _arith(op, a, b):
    """Exact interval arithmetic via corner evaluation (monotone ops)."""
    if isinstance(a, Interval) or isinstance(b, Interval):
        corners = [op(x, y) for x in (_lo(a), _hi(a))
                   for y in (_lo(b), _hi(b))]
        return Interval(min(corners), max(corners))
    return op(a, b)


class OperandVal:
    """A declared kernel operand: static shape/dtype + content range."""

    __slots__ = ("name", "shape", "dtype", "value_range")

    def __init__(self, name: str, shape: Tuple[int, ...], dtype: str,
                 value_range: Optional[Interval]):
        self.name, self.shape, self.dtype = name, tuple(shape), dtype
        self.value_range = value_range


class ClosureVal:
    """A lambda/def captured with its evaluation environment."""

    __slots__ = ("node", "env")

    def __init__(self, node: ast.AST, env: "Env"):
        self.node, self.env = node, env


class PartialVal:
    __slots__ = ("fn", "kwargs")

    def __init__(self, fn: ClosureVal, kwargs: Dict):
        self.fn, self.kwargs = fn, kwargs


class SpecVal:
    """An evaluated BlockSpec: concrete block shape + index_map closure."""

    __slots__ = ("block", "index_map", "node")

    def __init__(self, block, index_map, node: ast.AST):
        self.block, self.index_map, self.node = block, index_map, node


class StructVal:
    """An evaluated ShapeDtypeStruct."""

    __slots__ = ("shape", "dtype")

    def __init__(self, shape: Tuple[int, ...], dtype: str):
        self.shape, self.dtype = tuple(shape), dtype


class EvalError(Exception):
    """The site uses a construct the interpreter cannot bound."""


class Env:
    """Value bindings chained over lazily-evaluated AST assignments."""

    def __init__(self, values: Dict, ast_env: Dict[str, ast.AST],
                 parent: Optional["Env"] = None):
        self.values = values
        self.ast_env = ast_env
        self.parent = parent

    def child(self, values: Dict) -> "Env":
        return Env(values, self.ast_env, parent=self)

    def lookup(self, name: str):
        env: Optional[Env] = self
        while env is not None:
            if name in env.values:
                return env.values[name]
            env = env.parent
        raise KeyError(name)


class _Evaluator:
    """Evaluates the spec-defining subset of Python over abstract values."""

    def __init__(self, problems: List[Tuple[ast.AST, str]]):
        self.problems = problems
        self._depth = 0

    # -- expressions ----------------------------------------------------
    def eval(self, node: ast.AST, env: Env):
        self._depth += 1
        if self._depth > 200:
            raise EvalError("evaluation too deep (cyclic binding?)")
        try:
            return self._eval(node, env)
        finally:
            self._depth -= 1

    def _eval(self, node: ast.AST, env: Env):
        if isinstance(node, ast.Constant):
            return node.value
        if isinstance(node, ast.Name):
            try:
                return env.lookup(node.id)
            except KeyError:
                pass
            bound = env.ast_env.get(node.id)
            if bound is None:
                raise EvalError(f"unbound name '{node.id}' (bind it in the "
                                "contract sample or declare the operand)")
            if isinstance(bound, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return ClosureVal(bound, env)
            return self.eval(bound, env)
        if isinstance(node, (ast.Tuple, ast.List)):
            vals = [self.eval(e, env) for e in node.elts]
            return tuple(vals) if isinstance(node, ast.Tuple) else vals
        if isinstance(node, ast.BinOp):
            return self._binop(node, env)
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            v = self.eval(node.operand, env)
            return _arith(lambda a, b: a - b, 0, v) if isinstance(
                v, Interval) else -v
        if isinstance(node, ast.Compare):
            return self._compare(node, env)
        if isinstance(node, ast.Attribute):
            return self._attribute(node, env)
        if isinstance(node, ast.Subscript):
            return self._subscript(node, env)
        if isinstance(node, ast.Call):
            return self._call(node, env)
        if isinstance(node, ast.Lambda):
            return ClosureVal(node, env)
        if isinstance(node, ast.IfExp):
            test = self.eval(node.test, env)
            if not isinstance(test, bool):
                raise EvalError("conditional on a non-static test")
            return self.eval(node.body if test else node.orelse, env)
        if isinstance(node, ast.ListComp):
            return self._listcomp(node, env)
        if isinstance(node, ast.Starred):
            raise EvalError("starred expression inside a spec")
        raise EvalError(f"unsupported construct {type(node).__name__}")

    def _binop(self, node: ast.BinOp, env: Env):
        a = self.eval(node.left, env)
        b = self.eval(node.right, env)
        if isinstance(node.op, ast.Add):
            if isinstance(a, list) and isinstance(b, list):
                return a + b
            if isinstance(a, tuple) and isinstance(b, tuple):
                return a + b
            return _arith(lambda x, y: x + y, a, b)
        if isinstance(node.op, ast.Sub):
            return _arith(lambda x, y: x - y, a, b)
        if isinstance(node.op, ast.Mult):
            if isinstance(a, (tuple, list)) and isinstance(b, int):
                return a * b
            if isinstance(b, (tuple, list)) and isinstance(a, int):
                return b * a
            return _arith(lambda x, y: x * y, a, b)
        if isinstance(node.op, ast.FloorDiv):
            if _lo(b) <= 0 <= _hi(b):
                raise EvalError("floordiv by a range containing zero")
            return _arith(lambda x, y: x // y, a, b)
        if isinstance(node.op, ast.Mod):
            if isinstance(a, Interval) or isinstance(b, Interval):
                if _lo(b) <= 0:
                    raise EvalError("mod by a non-positive range")
                return Interval(0, _hi(b) - 1)
            return a % b
        raise EvalError(f"unsupported operator {type(node.op).__name__}")

    def _compare(self, node: ast.Compare, env: Env) -> bool:
        if len(node.ops) != 1:
            raise EvalError("chained comparison")
        a = self.eval(node.left, env)
        b = self.eval(node.comparators[0], env)
        if isinstance(a, Interval) or isinstance(b, Interval):
            raise EvalError("comparison on a grid-dependent value")
        table = {ast.Eq: lambda: a == b, ast.NotEq: lambda: a != b,
                 ast.Lt: lambda: a < b, ast.LtE: lambda: a <= b,
                 ast.Gt: lambda: a > b, ast.GtE: lambda: a >= b}
        fn = table.get(type(node.ops[0]))
        if fn is None:
            raise EvalError("unsupported comparison")
        return fn()

    def _attribute(self, node: ast.Attribute, env: Env):
        # operand handles expose the static facts kernels read
        try:
            base = self.eval(node.value, env)
        except EvalError:
            # module attribute (jnp.float32, pl.BlockSpec, ...): symbolic —
            # dtype-like leaves evaluate to their attribute name
            return node.attr
        if isinstance(base, OperandVal):
            if node.attr == "shape":
                return base.shape
            if node.attr == "ndim":
                return len(base.shape)
            if node.attr == "dtype":
                return base.dtype
            raise EvalError(f"operand attribute .{node.attr}")
        raise EvalError(f"attribute .{node.attr} on {type(base).__name__}")

    def _subscript(self, node: ast.Subscript, env: Env):
        base = self.eval(node.value, env)
        idx = self.eval(node.slice, env)
        if isinstance(base, OperandVal):
            indices = idx if isinstance(idx, tuple) else (idx,)
            if len(indices) != len(base.shape):
                self.problems.append((node, f"operand '{base.name}' "
                                      f"{base.shape} subscripted with "
                                      f"{len(indices)} indices"))
            for axis, (i, dim) in enumerate(zip(indices, base.shape)):
                if _lo(i) < 0 or _hi(i) >= dim:
                    self.problems.append((
                        node, f"index_map reads operand '{base.name}' axis "
                        f"{axis} at {Interval(_lo(i), _hi(i))} outside "
                        f"[0, {dim - 1}]"))
            if base.value_range is None:
                raise EvalError(f"operand '{base.name}' used as an index "
                                "table but declares no value_range")
            return Interval(base.value_range.lo, base.value_range.hi)
        if isinstance(base, (tuple, list)):
            if not isinstance(idx, int):
                raise EvalError("non-constant subscript of a tuple")
            return base[idx]
        raise EvalError(f"subscript of {type(base).__name__}")

    def _listcomp(self, node: ast.ListComp, env: Env):
        if len(node.generators) != 1 or node.generators[0].ifs:
            raise EvalError("unsupported comprehension shape")
        gen = node.generators[0]
        if not isinstance(gen.target, ast.Name):
            raise EvalError("comprehension target must be a name")
        seq = self.eval(gen.iter, env)
        if not isinstance(seq, (range, list, tuple)):
            raise EvalError("comprehension over a non-static sequence")
        return [self.eval(node.elt, env.child({gen.target.id: item}))
                for item in seq]

    def _call(self, node: ast.Call, env: Env):
        name = call_name(node)
        if name == "range":
            args = [self.eval(a, env) for a in node.args]
            if not all(isinstance(a, int) for a in args):
                raise EvalError("range() over non-static bounds")
            return range(*args)
        if name == "partial":
            fn = self.eval(node.args[0], env)
            if not isinstance(fn, ClosureVal):
                raise EvalError("partial of a non-function")
            kwargs = {kw.arg: self.eval(kw.value, env)
                      for kw in node.keywords if kw.arg}
            return PartialVal(fn, kwargs)
        if name == "BlockSpec":
            return self._blockspec(node, env)
        if name == "ShapeDtypeStruct":
            shape = self.eval(node.args[0], env)
            dtype = self.eval(node.args[1], env)
            if not isinstance(dtype, str):
                raise EvalError("non-static out_shape dtype")
            return StructVal(shape, dtype)
        if name == "len":
            v = self.eval(node.args[0], env)
            if isinstance(v, (tuple, list)):
                return len(v)
            raise EvalError("len() of a non-sequence")
        # factory call: the callee must resolve to a closure
        fn = self.eval(node.func, env)
        if isinstance(fn, (ClosureVal, PartialVal)):
            args = [self.eval(a, env) for a in node.args]
            kwargs = {kw.arg: self.eval(kw.value, env)
                      for kw in node.keywords if kw.arg}
            return self.call_function(fn, args, kwargs)
        raise EvalError(f"call of unsupported target '{name}'")

    def _blockspec(self, node: ast.Call, env: Env) -> SpecVal:
        block_node = kwarg(node, "block_shape") or (
            node.args[0] if node.args else None)
        map_node = kwarg(node, "index_map") or (
            node.args[1] if len(node.args) > 1 else None)
        block = self.eval(block_node, env) if block_node is not None else None
        index_map = self.eval(map_node, env) if map_node is not None else None
        if isinstance(block, tuple):
            # a None entry is a squeezed unit dim: block size 1
            block = tuple(1 if d is None else d for d in block)
        if block is not None and not (
                isinstance(block, tuple)
                and all(isinstance(d, int) for d in block)):
            raise EvalError(f"non-static block shape {block!r}")
        return SpecVal(block, index_map, node)

    # -- function application -------------------------------------------
    def call_function(self, fn, args: Sequence, kwargs: Dict):
        bound_kwargs = dict(kwargs)
        if isinstance(fn, PartialVal):
            bound_kwargs.update(fn.kwargs)
            fn = fn.fn
        node, env = fn.node, fn.env
        a = node.args
        params = [p.arg for p in (a.posonlyargs + a.args)]
        local: Dict = {}
        if len(args) > len(params):
            if a.vararg is None:
                raise EvalError(
                    f"index_map/factory takes {len(params)} args, got "
                    f"{len(args)} (grid + scalar-prefetch operands)")
            local[a.vararg.arg] = tuple(args[len(params):])
            args = args[:len(params)]
        if len(args) < len(params) - len(a.defaults):
            raise EvalError(
                f"index_map/factory takes {len(params)} args, got "
                f"{len(args)} (grid + scalar-prefetch operands)")
        local.update(zip(params, args))
        for p in a.kwonlyargs:
            if p.arg in bound_kwargs:
                local[p.arg] = bound_kwargs[p.arg]
        call_env = env.child(local)
        if isinstance(node, ast.Lambda):
            return self.eval(node.body, call_env)
        result = self._exec_body(node.body, call_env)
        if result is _NO_RETURN:
            raise EvalError(f"'{node.name}' never returns")
        return result

    def _exec_body(self, stmts: Sequence[ast.stmt], env: Env):
        for stmt in stmts:
            if isinstance(stmt, ast.Return):
                return self.eval(stmt.value, env) if stmt.value else None
            if isinstance(stmt, (ast.Delete, ast.Pass, ast.Expr)):
                continue
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 \
                    and isinstance(stmt.targets[0], ast.Name):
                env.values[stmt.targets[0].id] = self.eval(stmt.value, env)
                continue
            if isinstance(stmt, ast.If):
                test = self.eval(stmt.test, env)
                if not isinstance(test, bool):
                    raise EvalError("index_map branches on a grid value")
                result = self._exec_body(
                    stmt.body if test else stmt.orelse, env)
                if result is not _NO_RETURN:
                    return result
                continue
            raise EvalError(
                f"unsupported statement {type(stmt).__name__} in index_map")
        return _NO_RETURN


_NO_RETURN = object()


# ---------------------------------------------------------------------------
# contract resolution
# ---------------------------------------------------------------------------
_registry_cache: Optional[Tuple[Dict, Dict]] = None


def load_registry() -> Tuple[Dict, Dict]:
    """(CONTRACTS, PARTIAL_GROUPS) from the declared-contract module,
    loaded by file path so the import costs nothing (stdlib-only)."""
    global _registry_cache
    if _registry_cache is None:
        path = Path(__file__).resolve().parent.parent / _REGISTRY_REL
        spec = importlib.util.spec_from_file_location(
            "_replint_kernel_contracts", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)  # type: ignore[union-attr]
        _registry_cache = (mod.CONTRACTS, mod.PARTIAL_GROUPS)
    return _registry_cache


def _inline_tables(ctx: FileContext) -> Tuple[Optional[Dict], Dict]:
    """Literal ``REPLINT_KERNEL_CONTRACTS`` / ``REPLINT_PARTIAL_GROUPS``
    declared in the analyzed file (fixture support)."""
    table, groups = None, {}
    for stmt in ctx.tree.body:
        if not (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)):
            continue
        name = stmt.targets[0].id
        if name not in (INLINE_TABLE, INLINE_GROUPS):
            continue
        try:
            value = ast.literal_eval(stmt.value)
        except (ValueError, SyntaxError):
            continue
        if name == INLINE_TABLE:
            table = value
        else:
            groups = value
    return table, groups


def _resolve_sym(sym, sample: Dict, what: str):
    if isinstance(sym, int):
        return sym
    if isinstance(sym, str):
        if sym not in sample:
            raise EvalError(f"{what} symbol '{sym}' missing from sample")
        return sample[sym]
    raise EvalError(f"{what} entry {sym!r} is neither int nor symbol")


def _resolve_shape(shape, sample: Dict) -> Tuple[int, ...]:
    if isinstance(shape, str):  # whole shape bound per sample (rank varies)
        return tuple(_resolve_sym(shape, sample, "shape"))
    return tuple(_resolve_sym(s, sample, "shape") for s in shape)


def _expand_operands(contract: Dict, sample: Dict) -> List[OperandVal]:
    out: List[OperandVal] = []
    for op in contract.get("operands", ()):
        shape = _resolve_shape(op["shape"], sample)
        vr = op.get("value_range")
        rng = Interval(_resolve_sym(vr[0], sample, "value_range"),
                       _resolve_sym(vr[1], sample, "value_range")) \
            if vr is not None else None
        val = OperandVal(op["name"], shape, op.get("dtype", _F32), rng)
        out.extend([val] * _resolve_sym(op.get("repeat", 1), sample,
                                        "repeat"))
    return out


def _resolve_outputs(contract: Dict, sample: Dict
                     ) -> List[Tuple[Tuple[int, ...], str]]:
    return [(_resolve_shape(o["shape"], sample), o.get("dtype", _F32))
            for o in contract.get("outputs", ())]


def _parity_sample(contract: Dict) -> Optional[Dict]:
    hits = [s for s in contract.get("samples", ()) if s.get("_parity")]
    return hits[0] if len(hits) == 1 else None


# ---------------------------------------------------------------------------
# the per-site verification
# ---------------------------------------------------------------------------
def _as_list(v) -> list:
    return v if isinstance(v, list) else [v]


def _find_launch_parts(call: ast.Call) -> Dict[str, Optional[ast.AST]]:
    """grid / num_scalar_prefetch / in_specs / out_specs / out_shape AST
    nodes of a pallas_call, whether given flat or via a grid_spec."""
    parts = {"grid": kwarg(call, "grid"),
             "num_scalar_prefetch": None,
             "in_specs": kwarg(call, "in_specs"),
             "out_specs": kwarg(call, "out_specs"),
             "out_shape": kwarg(call, "out_shape")}
    gs = kwarg(call, "grid_spec")
    if isinstance(gs, ast.Call):
        for key in ("grid", "num_scalar_prefetch", "in_specs", "out_specs"):
            val = kwarg(gs, key)
            if val is not None:
                parts[key] = val
    return parts


def _check_spec(ev: _Evaluator, spec: SpecVal, op: OperandVal,
                axes: List, what: str) -> List[str]:
    """One BlockSpec against one operand under one sample binding."""
    msgs: List[str] = []
    if spec.block is None:
        return msgs
    if len(spec.block) != len(op.shape):
        msgs.append(f"{what} block shape {spec.block} has rank "
                    f"{len(spec.block)} but operand '{op.name}' has rank "
                    f"{len(op.shape)} {op.shape}")
        return msgs
    for axis, (bs, dim) in enumerate(zip(spec.block, op.shape)):
        if bs <= 0 or dim % bs:
            msgs.append(f"{what} block dim {bs} does not divide operand "
                        f"'{op.name}' axis {axis} (size {dim})")
    if spec.index_map is None:
        return msgs
    try:
        idx = ev.call_function(spec.index_map, list(axes), {})
    except EvalError as e:
        msgs.append(f"{what} index_map for operand '{op.name}': {e}")
        return msgs
    if not isinstance(idx, tuple):
        idx = (idx,)
    if len(idx) != len(op.shape):
        msgs.append(f"{what} index_map returns {len(idx)} block indices "
                    f"for rank-{len(op.shape)} operand '{op.name}'")
        return msgs
    for axis, (i, bs, dim) in enumerate(zip(idx, spec.block, op.shape)):
        lo, hi = _lo(i), _hi(i)
        if lo < 0 or (hi + 1) * bs > dim:
            msgs.append(
                f"{what} index_map addresses blocks {Interval(lo, hi)} × "
                f"block dim {bs} beyond operand '{op.name}' axis {axis} "
                f"(size {dim})")
    return msgs


def _check_site(ctx: FileContext, call: ast.Call, site: str,
                contract: Dict) -> List[Finding]:
    parts = _find_launch_parts(call)
    ast_env = scope_env(ctx, call)
    messages: Dict[str, Tuple[int, int]] = {}

    def add(msg: str, node: ast.AST = call):
        messages.setdefault(msg, (node.lineno, node.col_offset))

    for sample in contract.get("samples", ()):
        problems: List[Tuple[ast.AST, str]] = []
        ev = _Evaluator(problems)
        try:
            operands = _expand_operands(contract, sample)
            values = {k: v for k, v in sample.items()
                      if not k.startswith("_")}
            for op in operands:
                values.setdefault(op.name, op)
            env = Env(values, ast_env)

            # grid: site expression vs contract symbols
            if parts["grid"] is None:
                raise EvalError("pallas_call has no grid/grid_spec")
            grid = ev.eval(parts["grid"], env)
            want_grid = tuple(_resolve_sym(g, sample, "grid")
                              for g in contract.get("grid", ()))
            if tuple(grid) != want_grid:
                add(f"grid {tuple(grid)} != declared grid {want_grid}")
                continue
            axes = [Interval(0, n - 1) for n in grid]

            # scalar-prefetch split
            npf_decl = _resolve_sym(contract.get("num_scalar_prefetch", 0),
                                    sample, "num_scalar_prefetch")
            npf_node = parts["num_scalar_prefetch"]
            npf = ev.eval(npf_node, env) if npf_node is not None else 0
            if npf != npf_decl:
                add(f"num_scalar_prefetch {npf} != declared {npf_decl}")
                continue
            prefetch = operands[:npf]
            blocked = operands[npf:]
            axes_and_prefetch = axes + list(prefetch)

            # in_specs, positionally against the expanded operand list
            specs = _as_list(ev.eval(parts["in_specs"], env)) \
                if parts["in_specs"] is not None else []
            if len(specs) != len(blocked):
                add(f"{len(specs)} in_specs for {len(blocked)} declared "
                    f"non-prefetch operands "
                    f"(sample ppb={sample.get('ppb')})")
                continue
            for spec, op in zip(specs, blocked):
                if not isinstance(spec, SpecVal):
                    add(f"in_spec for operand '{op.name}' is not a "
                        "BlockSpec")
                    continue
                for msg in _check_spec(ev, spec, op, axes_and_prefetch,
                                       "in_spec"):
                    add(msg, spec.node)

            # out_shape vs the declared output contract
            outs = _resolve_outputs(contract, sample)
            structs = _as_list(ev.eval(parts["out_shape"], env)) \
                if parts["out_shape"] is not None else []
            if len(structs) != len(outs):
                add(f"{len(structs)} out_shape entries for {len(outs)} "
                    "declared outputs")
                continue
            group = contract.get("partial_group")
            out_ops = []
            for i, (st, (shape, dtype)) in enumerate(zip(structs, outs)):
                if not isinstance(st, StructVal):
                    add(f"out_shape[{i}] is not a ShapeDtypeStruct")
                    continue
                if st.shape != shape:
                    add(f"out_shape[{i}] {st.shape} != declared {shape}")
                if st.dtype != dtype:
                    tag = (f" (split-K '{group}' partials must be "
                           f"{dtype})" if group else "")
                    add(f"out_shape[{i}] dtype {st.dtype} != declared "
                        f"{dtype}{tag}")
                out_ops.append(OperandVal(f"out[{i}]", st.shape, st.dtype,
                                          None))

            # out_specs against the evaluated out_shape
            ospecs = _as_list(ev.eval(parts["out_specs"], env)) \
                if parts["out_specs"] is not None else []
            if len(ospecs) != len(out_ops):
                add(f"{len(ospecs)} out_specs for {len(out_ops)} outputs")
                continue
            for spec, op in zip(ospecs, out_ops):
                if not isinstance(spec, SpecVal):
                    continue
                for msg in _check_spec(ev, spec, op, axes_and_prefetch,
                                       "out_spec"):
                    add(msg, spec.node)
        except EvalError as e:
            add(f"could not verify against contract: {e}")
        for node, msg in problems:
            add(msg, node)

    return [Finding(rule=RULE, path=ctx.path, line=line, col=col,
                    symbol=site, message=msg)
            for msg, (line, col) in messages.items()]


# ---------------------------------------------------------------------------
# group-level checks: parity, handoff, fold
# ---------------------------------------------------------------------------
def _check_groups(path: str, contracts: Dict, groups: Dict) -> List[Finding]:
    findings: List[Finding] = []

    def add(symbol: str, msg: str):
        findings.append(Finding(rule=RULE, path=path, line=1, col=0,
                                symbol=symbol, message=msg))

    canonical: Dict[str, List[Tuple[Tuple[int, ...], str]]] = {}
    anchor: Dict[str, str] = {}
    for group in groups:
        members = [(site, c) for site, c in sorted(contracts.items())
                   if c.get("partial_group") == group]
        for site, contract in members:
            sample = _parity_sample(contract)
            if sample is None:
                add(site, f"partial group '{group}' member needs exactly "
                    "one sample marked _parity")
                continue
            try:
                outs = _resolve_outputs(contract, sample)
            except EvalError as e:
                add(site, f"could not resolve parity outputs: {e}")
                continue
            for i, (_, dtype) in enumerate(outs):
                if dtype != _F32:
                    add(site, f"partial group '{group}' output[{i}] "
                        f"declares dtype {dtype}; split-K (m, l, acc) "
                        "partials must be float32")
            if group not in canonical:
                canonical[group], anchor[group] = outs, site
            elif outs != canonical[group]:
                add(site, f"partial contract skew in group '{group}': "
                    f"{site} declares {outs} but {anchor[group]} declares "
                    f"{canonical[group]} (TPU/GPU parity broken)")

    # consumers must ingest exactly the group's partial shapes
    for site, contract in sorted(contracts.items()):
        consumes = contract.get("consumes")
        if not consumes:
            continue
        group = consumes.get("group")
        if group not in canonical:
            add(site, f"consumes unknown partial group '{group}'")
            continue
        sample = _parity_sample(contract)
        if sample is None:
            add(site, "consumer contract needs exactly one _parity sample")
            continue
        by_name = {op["name"]: op for op in contract.get("operands", ())}
        got = []
        try:
            for name in consumes.get("operands", ()):
                op = by_name.get(name)
                if op is None:
                    raise EvalError(f"consumed operand '{name}' not "
                                    "declared")
                got.append((_resolve_shape(op["shape"], sample),
                            op.get("dtype", _F32)))
        except EvalError as e:
            add(site, f"could not resolve consumed operands: {e}")
            continue
        if got != canonical[group]:
            add(site, f"handoff mismatch: consumes {got} but group "
                f"'{group}' emits {canonical[group]} "
                f"(declared by {anchor[group]})")

    # fold relations between groups (prefill q-block axis → decode batch)
    for group, meta in sorted(groups.items()):
        target = meta.get("folds_into")
        if not target:
            continue
        axis = meta.get("fold_axis", 0)
        if group not in canonical or target not in canonical:
            continue
        folded = [(s[:axis] + s[axis + 1:], d) for s, d in canonical[group]]
        if folded != canonical[target]:
            add(anchor[group],
                f"group '{group}' folded along axis {axis} gives {folded} "
                f"but group '{target}' emits {canonical[target]}")
    return findings


# ---------------------------------------------------------------------------
# the rule
# ---------------------------------------------------------------------------
def _enclosing_function(node: ast.AST) -> Optional[str]:
    cur = getattr(node, "_replint_parent", None)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return cur.name
        cur = getattr(cur, "_replint_parent", None)
    return None


def _is_registry_file(path: str) -> bool:
    return path.startswith("src/") and \
        path.endswith(_REGISTRY_REL.as_posix())


@register(
    RULE,
    "abstract interpretation of pallas_call launches against the declared "
    "kernel contracts: BlockSpec rank/divisibility, in-range index_maps "
    "over every grid point, f32 split-K partials, decode/prefill/combine "
    "handoff and TPU≡GPU parity",
    dirs=("kernels",))
def check(ctx: FileContext, project: Project) -> List[Finding]:
    inline_table, inline_groups = _inline_tables(ctx)
    if inline_table is not None:
        contracts, groups = inline_table, inline_groups
        require_contract = True
    elif ctx.path.startswith("src/"):
        contracts, groups = load_registry()
        require_contract = True
    else:
        # fixtures/examples without an inline table opt out entirely
        return []

    findings: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not (isinstance(node, ast.Call)
                and call_name(node) == "pallas_call"):
            continue
        site = _enclosing_function(node)
        contract = contracts.get(site) if site else None
        if contract is None:
            if require_contract:
                findings.append(Finding(
                    rule=RULE, path=ctx.path, line=node.lineno,
                    col=node.col_offset, symbol=site or "<module>",
                    message=f"pallas_call in '{site}' has no declared "
                    f"kernel contract (add it to "
                    f"{_REGISTRY_REL.as_posix()} or {INLINE_TABLE})"))
            continue
        findings.extend(_check_site(ctx, node, site, contract))

    if inline_table is not None or _is_registry_file(ctx.path):
        findings.extend(_check_groups(ctx.path, contracts, groups))
    return findings
