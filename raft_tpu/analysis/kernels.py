"""graft-kern engine 4: static Pallas kernel verifier.

GL006's literal-BlockSpec heuristic could only judge geometry written
as integer literals — exactly the form docs/kernels.md BANS for real
kernels (tile budgets must be expression-derived). This engine closes
that hole by *abstract interpretation*: for every ``pl.pallas_call``
site it mini-interprets the enclosing function under a set of concrete
shape **bindings** — drawn from the kernel's registered contract
(:mod:`raft_tpu.analysis.contracts`), from the tuning layer's
tile-candidate enumeration (``tuning.kernel_shape_candidates()`` — the
values a dispatch-table winner string like ``fused_fold:2048`` can
inject), and from literal defaults — evaluating BlockSpec shapes,
index maps, grids, scratch shapes, and out_shapes the way the tracer
would, including calls into the module's own pure geometry helpers
(``candidate_width``, ``fold_depth``, ``packed_row_layout``, ...).

Checks per resolved site (rule catalog in docs/static_analysis.md):

GL006  exact VMEM accounting — blocks + scratch at their real dtypes
       against the per-core budget (replaces the literal heuristic;
       the literal screen remains only for sites the evaluator cannot
       resolve).
GL015  index-map bounds — every BlockSpec index map evaluated over the
       grid corner extents must stay inside the (padded) array shape —
       and reachable non-divisible tails (a grid extent computed as
       ``ceil(n/t)`` with ``n % t != 0`` under some binding) require
       tail-mask evidence in the kernel body; floor-divided extents
       that drop remainder rows are flagged outright.
GL016  tile alignment — block dims checked against the real Mosaic
       rule per dtype ((8,128) f32, (16,128) bf16, (32,128) int8):
       a dim is legal when it is a multiple of the minimum, is 1, or
       equals the full array dim; violations name the dim.
GL017  grid hazards — an output ref whose index map ignores a grid
       dimension of extent > 1 is revisited across steps; plain
       overwrites lose partial results and read-modify-write
       accumulation without a first-step init reads uninitialized
       memory.
GL018  MXU dtype audit — ``dot_general``/``jnp.dot`` operands with
       provably different dtypes (silent promotion off the MXU), or
       sub-f32 operands with no ``preferred_element_type`` (accumulator
       stays low-precision).

Interpretation is *per concrete binding*: guards that ``raise`` under a
binding prune it (the kernel's own eligibility checks are respected),
so findings come with a witness binding in the message. The same
contract cases also drive the dynamic interpret-mode sweep
(``tests/test_kernel_contracts.py``) — static engine and dynamic sweep
cross-check each other.
"""

from __future__ import annotations

import ast
import dataclasses
import importlib
import itertools
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from raft_tpu.analysis.contracts import (
    LANE,
    SUBLANE_BY_ITEMSIZE,
    dtype_itemsize,
    static_cases,
)
from raft_tpu.analysis.rules import (
    Finding,
    apply_suppressions,
    scan_suppressions,
)

_VMEM_BUDGET_BYTES = 16 * 1024 * 1024   # ~VMEM per core (pallas guide)
_MAX_BINDINGS = 128                      # per site
_MAX_STEPS = 4000                        # interpreter fuel per binding
_MAX_LOOP = 256

_BLOCKSPEC_NAMES = ("pl.BlockSpec", "pallas.BlockSpec", "BlockSpec")
_VMEM_SCRATCH_NAMES = ("pltpu.VMEM", "tpu.VMEM")
_PALLAS_CALL_NAMES = ("pl.pallas_call", "pallas_call")
_GRIDSPEC_NAMES = ("pltpu.PrefetchScalarGridSpec", "PrefetchScalarGridSpec")
_SDS_NAMES = ("jax.ShapeDtypeStruct", "ShapeDtypeStruct")
_DOT_NAMES = ("jax.lax.dot_general", "lax.dot_general", "jnp.dot",
              "jnp.matmul", "jnp.einsum")

# fallback candidates for free dim names at UNCONTRACTED sites (fixture
# files / future kernels); contracted sites bind from their contract
_DEFAULT_DIMS: Dict[str, Tuple] = {
    "k": (1, 10, 129),
    "m": (16,), "n": (1000,), "d": (32,),
    "cap": (256,), "G": (8,), "nb": (4,), "C": (4,),
    "metric_kind": (0, 1),
}

_DTYPE_NAMES = {
    "jnp.float32": "float32", "np.float32": "float32",
    "jnp.bfloat16": "bfloat16", "jnp.float16": "float16",
    "jnp.int32": "int32", "np.int32": "int32", "jnp.uint32": "uint32",
    "jnp.int8": "int8", "jnp.uint8": "uint8", "jnp.int16": "int16",
    "jnp.bool_": "bool", "jnp.float64": "float64", "np.float64": "float64",
}


def _dotted(node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


# ---------------------------------------------------------------------------
# abstract values
# ---------------------------------------------------------------------------


class _Unknown:
    _inst = None

    def __new__(cls):
        if cls._inst is None:
            cls._inst = super().__new__(cls)
        return cls._inst

    def __repr__(self):
        return "<?>"


UNKNOWN = _Unknown()


class IntV(int):
    """A concrete int carrying one step of divisibility provenance:
    ``kind`` is "ceil"/"floor" when the value came directly from
    ``ceil(num/den)`` / ``num // den``; ``tail`` records whether
    ``num % den != 0`` under the active binding."""

    kind = None
    tail = False
    num = None
    den = None

    @classmethod
    def div(cls, value, kind, num, den):
        v = cls(value)
        v.kind = kind
        v.tail = (num % den) != 0 if den else False
        v.num, v.den = int(num), int(den)
        return v


@dataclasses.dataclass
class Arr:
    """An array value: shape entries are ints, dim-name strings (bound
    lazily against the binding), or UNKNOWN; dtype is a dtype name
    string, a ("dtype_of", name) token, or None when unknown."""

    shape: Optional[list] = None     # mutable: unpacking refines it
    dtype: object = None


@dataclasses.dataclass
class Lam:
    node: ast.Lambda
    env: dict


@dataclasses.dataclass
class FnV:
    node: ast.FunctionDef


@dataclasses.dataclass
class PartialV:
    fn: object
    kwargs: dict


@dataclasses.dataclass
class RealFn:
    """A helper resolved to the real imported callable (raft_tpu
    modules only) — called with concrete args, guarded."""

    fn: object


@dataclasses.dataclass
class BlockV:
    shape: Optional[tuple]          # tuple of int/UNKNOWN, or None
    index_map: Optional[Lam]
    lineno: int
    node: ast.Call = None


@dataclasses.dataclass
class ScratchV:
    shape: Optional[tuple]
    dtype: object
    lineno: int
    node: ast.Call = None


@dataclasses.dataclass
class SDSV:                          # jax.ShapeDtypeStruct
    shape: Optional[tuple]
    dtype: object


@dataclasses.dataclass
class GridSpecV:
    num_scalar_prefetch: int
    grid: tuple
    in_specs: list
    out_specs: list
    scratch: list


@dataclasses.dataclass
class SiteEval:
    """One pallas_call site fully evaluated under one binding."""

    binding: dict
    kernel: object                   # FnV | PartialV | UNKNOWN
    grid: tuple
    in_specs: list
    out_specs: list
    out_shapes: list                 # SDSV per output
    scratch: list
    inputs: list                     # Arr/UNKNOWN per runtime operand
    num_prefetch: int = 0


class _Infeasible(Exception):
    """The binding violates a guard the function itself raises on."""


class _Return(Exception):
    def __init__(self, value):
        self.value = value


class _OutOfFuel(Exception):
    pass


# ---------------------------------------------------------------------------
# the mini-interpreter
# ---------------------------------------------------------------------------


class Interp:
    def __init__(self, tree: ast.Module, module_name: Optional[str]):
        self.tree = tree
        self.module_name = module_name
        self.fns: Dict[str, ast.FunctionDef] = {}
        self.consts: Dict[str, object] = {}
        self._imports: Dict[str, Tuple[str, str]] = {}  # name -> (mod, attr)
        for node in tree.body:
            if isinstance(node, ast.FunctionDef):
                self.fns[node.name] = node
            elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                    and isinstance(node.targets[0], ast.Name) \
                    and isinstance(node.value, ast.Constant):
                self.consts[node.targets[0].id] = node.value.value
            elif isinstance(node, ast.ImportFrom) and node.module and \
                    node.module.startswith("raft_tpu"):
                for alias in node.names:
                    self._imports[alias.asname or alias.name] = (
                        node.module, alias.name)
        self.fuel = 0
        self.sites: Dict[ast.Call, SiteEval] = {}
        self.binding: dict = {}

    # -- entry -------------------------------------------------------------

    def run_function(self, fn: ast.FunctionDef, binding: dict,
                     arrays: Dict[str, tuple]) -> dict:
        """Interpret ``fn`` under ``binding``; populates ``self.sites``
        for pallas_call nodes reached. Returns the final env."""
        self.fuel = _MAX_STEPS
        self.binding = binding
        env = self._param_env(fn, binding, arrays)
        try:
            self._exec(fn.body, env)
        except _Return:
            pass
        return env

    def _param_env(self, fn: ast.FunctionDef, binding: dict,
                   arrays: Dict[str, tuple]) -> dict:
        env: dict = {}
        args = fn.args
        params = list(args.posonlyargs) + list(args.args) + list(args.kwonlyargs)
        defaults: Dict[str, object] = {}
        pos = args.posonlyargs + args.args
        for a, dflt in zip(pos[len(pos) - len(args.defaults):], args.defaults):
            defaults[a.arg] = self._eval(dflt, {})
        for a, dflt in zip(args.kwonlyargs, args.kw_defaults):
            if dflt is not None:
                defaults[a.arg] = self._eval(dflt, {})
        for p in params:
            name = p.arg
            if name in binding:
                v = binding[name]
                if isinstance(v, Arr):
                    env[name] = Arr(shape=list(v.shape) if v.shape else None,
                                    dtype=v.dtype)
                elif v is True and name in arrays:
                    env[name] = self._mk_arr(name, binding, arrays)
                elif v is None or v is False:
                    env[name] = None if name in arrays or v is None else v
                elif isinstance(v, bool):
                    env[name] = v
                elif isinstance(v, (int, str, float)):
                    env[name] = v
                else:
                    env[name] = UNKNOWN
            elif name in arrays:
                dflt = defaults.get(name, "__missing__")
                env[name] = (None if dflt is None
                             else self._mk_arr(name, binding, arrays))
            elif name in defaults:
                env[name] = defaults[name]
            else:
                env[name] = UNKNOWN
        return env

    def _mk_arr(self, name: str, binding: dict,
                arrays: Dict[str, tuple]) -> Arr:
        shape_decl = binding.get(f"{name}_shape", arrays.get(name))
        shape = None
        if shape_decl is not None:
            shape = [binding.get(d, d) if isinstance(d, str) else int(d)
                     for d in shape_decl]
            shape = [s if isinstance(s, (int, str)) else UNKNOWN
                     for s in shape]
        dtype = binding.get(f"{name}_dtype", binding.get("dtype"))
        return Arr(shape=shape, dtype=dtype)

    # -- statements --------------------------------------------------------

    def _tick(self):
        self.fuel -= 1
        if self.fuel <= 0:
            raise _OutOfFuel()

    def _exec(self, stmts: Sequence[ast.stmt], env: dict) -> None:
        for s in stmts:
            self._exec_one(s, env)

    def _exec_one(self, s: ast.stmt, env: dict) -> None:
        self._tick()
        if isinstance(s, ast.Assign):
            val = self._eval(s.value, env)
            for t in s.targets:
                self._assign(t, val, env, s.value)
        elif isinstance(s, ast.AugAssign):
            cur = self._eval(s.target, env) if isinstance(
                s.target, ast.Name) else UNKNOWN
            rhs = self._eval(s.value, env)
            val = self._binop(type(s.op), cur, rhs)
            if isinstance(s.target, ast.Name):
                env[s.target.id] = val
        elif isinstance(s, ast.AnnAssign):
            if s.value is not None and isinstance(s.target, ast.Name):
                env[s.target.id] = self._eval(s.value, env)
        elif isinstance(s, ast.Expr):
            self._eval(s.value, env)
        elif isinstance(s, ast.If):
            cond = self._truth(self._eval(s.test, env))
            if cond is UNKNOWN:
                self._exec_both(s.body, s.orelse, env)
            elif cond:
                self._exec(s.body, env)
            else:
                self._exec(s.orelse, env)
        elif isinstance(s, ast.While):
            it = 0
            while True:
                cond = self._truth(self._eval(s.test, env))
                if cond is UNKNOWN:
                    self._poison_assigned(s.body, env)
                    break
                if not cond:
                    break
                self._exec(s.body, env)
                it += 1
                if it > _MAX_LOOP:
                    self._poison_assigned(s.body, env)
                    break
        elif isinstance(s, ast.For):
            seq = self._eval(s.iter, env)
            if isinstance(seq, (list, tuple)) and len(seq) <= _MAX_LOOP:
                for item in seq:
                    self._assign(s.target, item, env, s.iter)
                    self._exec(s.body, env)
            else:
                self._assign(s.target, UNKNOWN, env, s.iter)
                self._poison_assigned(s.body, env)
        elif isinstance(s, ast.Raise):
            raise _Infeasible()
        elif isinstance(s, ast.Assert):
            cond = self._truth(self._eval(s.test, env))
            if cond is False:
                raise _Infeasible()
        elif isinstance(s, ast.Return):
            raise _Return(self._eval(s.value, env) if s.value else None)
        elif isinstance(s, ast.ImportFrom):
            if s.module and s.module.startswith("raft_tpu"):
                for alias in s.names:
                    env[alias.asname or alias.name] = self._resolve_import(
                        s.module, alias.name)
        elif isinstance(s, (ast.FunctionDef, ast.Import, ast.Pass,
                            ast.With, ast.Try, ast.Delete, ast.Global,
                            ast.Nonlocal)):
            if isinstance(s, ast.FunctionDef):
                env[s.name] = FnV(s)
            elif isinstance(s, ast.With):
                self._exec(s.body, env)
            elif isinstance(s, ast.Try):
                self._exec(s.body, env)
        # other statements: ignored

    def _exec_both(self, body, orelse, env: dict) -> None:
        e1 = dict(env)
        e2 = dict(env)
        try:
            self._exec(body, e1)
        except _Infeasible:
            e1 = None
        try:
            self._exec(orelse, e2)
        except _Infeasible:
            e2 = None
        if e1 is None and e2 is None:
            raise _Infeasible()
        if e1 is None:
            env.update(e2)
            return
        if e2 is None:
            env.update(e1)
            return
        for k in set(e1) | set(e2):
            a, b = e1.get(k, UNKNOWN), e2.get(k, UNKNOWN)
            env[k] = a if _same(a, b) else UNKNOWN

    def _poison_assigned(self, body, env: dict) -> None:
        for sub in ast.walk(ast.Module(body=list(body), type_ignores=[])):
            if isinstance(sub, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = sub.targets if isinstance(sub, ast.Assign) \
                    else [sub.target]
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            env[n.id] = UNKNOWN

    def _assign(self, target: ast.AST, val, env: dict,
                value_node: ast.AST) -> None:
        if isinstance(target, ast.Name):
            env[target.id] = val
            # name-provenance: `n = X.shape[0]` names X's dim 0 "n"
            self._note_shape_name(value_node, (target.id,), env, single=True)
        elif isinstance(target, (ast.Tuple, ast.List)):
            names = [t.id if isinstance(t, ast.Name) else None
                     for t in target.elts]
            if isinstance(val, (tuple, list)) and len(val) == len(target.elts):
                for t, v in zip(target.elts, val):
                    if isinstance(t, ast.Name):
                        env[t.id] = v
            else:
                for t in target.elts:
                    if isinstance(t, ast.Name):
                        env[t.id] = self.binding.get(t.id, UNKNOWN)
            self._note_shape_name(value_node, tuple(names), env, single=False)

    def _note_shape_name(self, value_node, names, env, single: bool) -> None:
        """Refine an Arr's symbolic shape from unpack targets:
        ``m, d = q.shape`` establishes q.shape == (m, d); ``n =
        x.shape[0]`` establishes x.shape[0] == n. Unbound dim names
        resolve through the active binding."""
        node = value_node
        idx = None
        if single and isinstance(node, ast.Subscript) and \
                isinstance(node.slice, ast.Constant) and \
                isinstance(node.slice.value, int):
            idx = node.slice.value
            node = node.value
        if not (isinstance(node, ast.Attribute) and node.attr == "shape"):
            return
        arr = self._eval(node.value, env)
        if not isinstance(arr, Arr):
            return
        if single:
            if idx is None:
                return
            name = names[0]
            if arr.shape is None:
                arr.shape = [UNKNOWN] * (idx + 1)
            while len(arr.shape) <= idx:
                arr.shape.append(UNKNOWN)
            if arr.shape[idx] is UNKNOWN and name:
                arr.shape[idx] = self.binding.get(name, name)
                env[name] = self.binding.get(name, UNKNOWN)
        else:
            if arr.shape is None:
                arr.shape = [UNKNOWN] * len(names)
            if len(arr.shape) == len(names):
                for i, name in enumerate(names):
                    if arr.shape[i] is UNKNOWN and name:
                        arr.shape[i] = self.binding.get(name, name)
                        env[name] = self.binding.get(name, UNKNOWN)

    # -- expressions -------------------------------------------------------

    def _truth(self, v):
        if v is UNKNOWN:
            return UNKNOWN
        if isinstance(v, Arr):
            return UNKNOWN
        try:
            return bool(v)
        except Exception:  # noqa: BLE001 - abstract value truthiness
            return UNKNOWN

    def _eval(self, node: ast.AST, env: dict):
        self._tick()
        if node is None:
            return None
        if isinstance(node, ast.Constant):
            return node.value
        if isinstance(node, ast.Name):
            if node.id in env:
                return env[node.id]
            if node.id in self.consts:
                return self.consts[node.id]
            if node.id in self.fns:
                return FnV(self.fns[node.id])
            if node.id in self._imports:
                return self._resolve_import(*self._imports[node.id])
            return UNKNOWN
        if isinstance(node, (ast.Tuple, ast.List)):
            return [self._eval(e, env) for e in node.elts] \
                if isinstance(node, ast.List) \
                else tuple(self._eval(e, env) for e in node.elts)
        if isinstance(node, ast.Dict):
            out = {}
            for k, v in zip(node.keys, node.values):
                kk = self._eval(k, env) if k is not None else UNKNOWN
                out[kk if not isinstance(kk, _Unknown) else object()] = \
                    self._eval(v, env)
            return out
        if isinstance(node, ast.Lambda):
            return Lam(node, dict(env))
        if isinstance(node, ast.BinOp):
            return self._binop(type(node.op), self._eval(node.left, env),
                               self._eval(node.right, env), node)
        if isinstance(node, ast.UnaryOp):
            v = self._eval(node.operand, env)
            if v is UNKNOWN:
                return UNKNOWN
            try:
                if isinstance(node.op, ast.USub):
                    # the ceil-div idiom: -(-n // t) — keep provenance
                    if isinstance(v, IntV) and v.kind == "neg_floor":
                        return IntV.div(-int(v), "ceil", v.num, v.den)
                    return -v
                if isinstance(node.op, ast.UAdd):
                    return +v
                if isinstance(node.op, ast.Not):
                    t = self._truth(v)
                    return UNKNOWN if t is UNKNOWN else not t
                if isinstance(node.op, ast.Invert):
                    return ~v
            except Exception:  # noqa: BLE001
                return UNKNOWN
            return UNKNOWN
        if isinstance(node, ast.BoolOp):
            vals = [self._eval(v, env) for v in node.values]
            truths = [self._truth(v) for v in vals]
            if isinstance(node.op, ast.And):
                if False in truths:
                    return False
                return UNKNOWN if UNKNOWN in truths else vals[-1]
            if True in truths:
                return next(v for v, t in zip(vals, truths) if t is True)
            return UNKNOWN if UNKNOWN in truths else vals[-1]
        if isinstance(node, ast.Compare):
            left = self._eval(node.left, env)
            result = True
            for op, comp in zip(node.ops, node.comparators):
                right = self._eval(comp, env)
                r = self._compare(op, left, right)
                if r is UNKNOWN:
                    return UNKNOWN
                result = result and r
                left = right
            return result
        if isinstance(node, ast.IfExp):
            cond = self._truth(self._eval(node.test, env))
            if cond is UNKNOWN:
                a = self._eval(node.body, env)
                b = self._eval(node.orelse, env)
                return a if _same(a, b) else UNKNOWN
            return self._eval(node.body if cond else node.orelse, env)
        if isinstance(node, ast.Attribute):
            return self._attr(node, env)
        if isinstance(node, ast.Subscript):
            return self._subscript(node, env)
        if isinstance(node, ast.Call):
            return self._call(node, env)
        if isinstance(node, ast.Starred):
            return self._eval(node.value, env)
        if isinstance(node, (ast.ListComp, ast.GeneratorExp)):
            return self._comprehension(node, env)
        if isinstance(node, ast.JoinedStr):
            return UNKNOWN
        return UNKNOWN

    def _binop(self, op, a, b, node=None):
        if a is UNKNOWN or b is UNKNOWN:
            return UNKNOWN
        try:
            if op is ast.Add:
                return a + b
            if op is ast.Sub:
                return a - b
            if op is ast.Mult:
                return a * b
            if op is ast.FloorDiv:
                v = a // b
                # the ceil-div idiom -(-a // b) surfaces here with a
                # negative numerator; tag plain positive floor-divs
                if isinstance(a, int) and isinstance(b, int) and b > 0:
                    if a >= 0:
                        return IntV.div(v, "floor", a, b)
                    return IntV.div(v, "neg_floor", -a, b)
                return v
            if op is ast.Mod:
                return a % b
            if op is ast.Div:
                return a / b
            if op is ast.Pow:
                return a ** b if abs(b) < 64 else UNKNOWN
            if op is ast.LShift:
                return a << b if b < 64 else UNKNOWN
            if op is ast.RShift:
                return a >> b
            if op is ast.BitOr:
                return a | b
            if op is ast.BitAnd:
                return a & b
            if op is ast.BitXor:
                return a ^ b
        except Exception:  # noqa: BLE001
            return UNKNOWN
        return UNKNOWN

    def _compare(self, op, a, b):
        if isinstance(op, (ast.Is, ast.IsNot)):
            if a is UNKNOWN or b is UNKNOWN:
                return UNKNOWN
            r = (a is None and b is None) or (a is b)
            if isinstance(a, Arr) and b is None:
                r = False
            if isinstance(b, Arr) and a is None:
                r = False
            return r if isinstance(op, ast.Is) else not r
        if a is UNKNOWN or b is UNKNOWN or isinstance(a, Arr) \
                or isinstance(b, Arr):
            return UNKNOWN
        try:
            if isinstance(op, ast.Eq):
                return a == b
            if isinstance(op, ast.NotEq):
                return a != b
            if isinstance(op, ast.Lt):
                return a < b
            if isinstance(op, ast.LtE):
                return a <= b
            if isinstance(op, ast.Gt):
                return a > b
            if isinstance(op, ast.GtE):
                return a >= b
            if isinstance(op, ast.In):
                return a in b
            if isinstance(op, ast.NotIn):
                return a not in b
        except Exception:  # noqa: BLE001
            return UNKNOWN
        return UNKNOWN

    def _attr(self, node: ast.Attribute, env: dict):
        base = self._eval(node.value, env)
        if isinstance(base, Arr):
            if node.attr == "shape":
                if base.shape is None:
                    return UNKNOWN
                return tuple(self.binding.get(d, UNKNOWN)
                             if isinstance(d, str) else d
                             for d in base.shape)
            if node.attr == "dtype":
                return base.dtype if base.dtype is not None else UNKNOWN
            if node.attr == "ndim":
                return len(base.shape) if base.shape is not None else UNKNOWN
        dotted = _dotted(node)
        if dotted in _DTYPE_NAMES:
            return _DTYPE_NAMES[dotted]
        if isinstance(base, dict) and node.attr in base:
            return base[node.attr]
        return UNKNOWN

    def _subscript(self, node: ast.Subscript, env: dict):
        base = self._eval(node.value, env)
        if base is UNKNOWN:
            return UNKNOWN
        if isinstance(base, Arr):
            return Arr(shape=None, dtype=base.dtype)
        sl = node.slice
        if isinstance(sl, ast.Slice):
            lo = self._eval(sl.lower, env) if sl.lower else None
            hi = self._eval(sl.upper, env) if sl.upper else None
            if lo is UNKNOWN or hi is UNKNOWN:
                return UNKNOWN
            try:
                return base[slice(lo, hi)]
            except Exception:  # noqa: BLE001
                return UNKNOWN
        idx = self._eval(sl, env)
        if idx is UNKNOWN:
            return UNKNOWN
        try:
            return base[idx]
        except Exception:  # noqa: BLE001
            return UNKNOWN

    def _comprehension(self, node, env: dict):
        if len(node.generators) != 1:
            return UNKNOWN
        gen = node.generators[0]
        seq = self._eval(gen.iter, env)
        if not isinstance(seq, (list, tuple, range)) or len(seq) > _MAX_LOOP:
            return UNKNOWN
        out = []
        for item in seq:
            inner = dict(env)
            self._assign(gen.target, item, inner, gen.iter)
            keep = True
            for cond in gen.ifs:
                t = self._truth(self._eval(cond, inner))
                if t is UNKNOWN:
                    return UNKNOWN
                keep = keep and t
            if keep:
                out.append(self._eval(node.elt, inner))
        return out

    def _resolve_import(self, module: str, attr: str):
        try:
            return RealFn(getattr(importlib.import_module(module), attr))
        except Exception:  # noqa: BLE001 - unresolvable helper
            return UNKNOWN

    # -- calls -------------------------------------------------------------

    def _call(self, node: ast.Call, env: dict):
        fname = _dotted(node.func) or ""

        # method calls on abstract values
        if isinstance(node.func, ast.Attribute):
            meth = node.func.attr
            base = self._eval(node.func.value, env)
            if isinstance(base, Arr):
                if meth == "reshape":
                    shape = [self._eval(a, env) for a in node.args]
                    if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
                        shape = list(shape[0])
                    if all(isinstance(s, int) for s in shape):
                        return Arr(shape=list(shape), dtype=base.dtype)
                    return Arr(shape=None, dtype=base.dtype)
                if meth == "astype":
                    dt = self._eval(node.args[0], env) if node.args else None
                    return Arr(shape=list(base.shape) if base.shape else None,
                               dtype=dt if isinstance(dt, str) else
                               (dt if isinstance(dt, tuple) else None))
                return Arr(shape=None, dtype=base.dtype)
            if isinstance(base, list):
                if meth == "append":
                    base.append(self._eval(node.args[0], env))
                    return None
                if meth == "extend":
                    v = self._eval(node.args[0], env)
                    if isinstance(v, (list, tuple)):
                        base.extend(v)
                    return None
                if meth == "pop":
                    idx = self._eval(node.args[0], env) if node.args else -1
                    try:
                        return base.pop(idx)
                    except Exception:  # noqa: BLE001
                        return UNKNOWN
            if isinstance(base, int) and meth == "bit_length":
                return int(base).bit_length()

        args = [self._eval(a, env) for a in node.args]
        # splat starred args
        flat_args: list = []
        for a, n in zip(args, node.args):
            if isinstance(n, ast.Starred) and isinstance(a, (list, tuple)):
                flat_args.extend(a)
            else:
                flat_args.append(a)
        kwargs = {kw.arg: self._eval(kw.value, env)
                  for kw in node.keywords if kw.arg}

        if fname in _BLOCKSPEC_NAMES:
            shape = None
            imap = None
            if node.args:
                v = flat_args[0]
                if isinstance(v, (tuple, list)):
                    shape = tuple(v)
                elif v is not UNKNOWN and isinstance(v, Lam):
                    imap = v        # BlockSpec(index_map) legacy order
            if len(node.args) >= 2 and isinstance(args[1], Lam):
                imap = args[1]
            if isinstance(kwargs.get("index_map"), Lam):
                imap = kwargs["index_map"]
            if isinstance(kwargs.get("block_shape"), (tuple, list)):
                shape = tuple(kwargs["block_shape"])
            return BlockV(shape, imap, node.lineno, node)
        if fname in _VMEM_SCRATCH_NAMES:
            shape = flat_args[0] if flat_args else kwargs.get("shape")
            dtype = flat_args[1] if len(flat_args) > 1 else kwargs.get("dtype")
            return ScratchV(tuple(shape) if isinstance(shape, (tuple, list))
                            else None, dtype, node.lineno, node)
        if fname in _SDS_NAMES:
            shape = flat_args[0] if flat_args else kwargs.get("shape")
            dtype = flat_args[1] if len(flat_args) > 1 else kwargs.get("dtype")
            return SDSV(tuple(shape) if isinstance(shape, (tuple, list))
                        else None, dtype)
        if fname in _GRIDSPEC_NAMES:
            return GridSpecV(
                num_scalar_prefetch=int(kwargs.get("num_scalar_prefetch", 0))
                if isinstance(kwargs.get("num_scalar_prefetch", 0), int)
                else 0,
                grid=kwargs.get("grid") or (),
                in_specs=kwargs.get("in_specs") or [],
                out_specs=kwargs.get("out_specs") or [],
                scratch=list(kwargs.get("scratch_shapes") or []),
            )
        if fname in _PALLAS_CALL_NAMES:
            return self._eval_site(node, flat_args, kwargs)
        if fname in ("functools.partial", "partial"):
            return PartialV(flat_args[0] if flat_args else UNKNOWN, kwargs)
        if fname in ("pl.cdiv", "cdiv"):
            if len(flat_args) == 2 and all(
                    isinstance(a, int) for a in flat_args):
                a, b = flat_args
                return IntV.div(-(-a // b), "ceil", a, b)
            return UNKNOWN
        if fname == "jnp.pad" or fname == "np.pad":
            return self._eval_pad(node, flat_args, env)
        if fname in ("jnp.zeros", "jnp.ones", "jnp.empty", "jnp.full",
                     "np.zeros", "np.ones", "np.empty", "np.full"):
            shape = flat_args[0] if flat_args else None
            if isinstance(shape, int):
                shape = (shape,)
            dt = kwargs.get("dtype")
            if len(flat_args) > 1 and isinstance(flat_args[-1], str):
                dt = flat_args[-1]
            return Arr(shape=list(shape) if isinstance(shape, (tuple, list))
                       and all(isinstance(s, int) for s in shape) else None,
                       dtype=dt if isinstance(dt, str) else None)
        if fname in ("int", "bool", "float", "str"):
            v = flat_args[0] if flat_args else 0
            if v is UNKNOWN or isinstance(v, Arr):
                return UNKNOWN
            try:
                return {"int": int, "bool": bool, "float": float,
                        "str": str}[fname](v)
            except Exception:  # noqa: BLE001
                return UNKNOWN
        if fname in ("len",):
            v = flat_args[0] if flat_args else UNKNOWN
            if isinstance(v, (list, tuple, dict, str)):
                return len(v)
            if isinstance(v, Arr) and v.shape is not None:
                return len(v.shape)
            return UNKNOWN
        if fname in ("max", "min", "abs", "sum"):
            if any(a is UNKNOWN or isinstance(a, Arr) for a in flat_args):
                return UNKNOWN
            try:
                vals = (flat_args[0] if len(flat_args) == 1
                        and isinstance(flat_args[0], (list, tuple))
                        else flat_args)
                return {"max": max, "min": min, "abs": abs,
                        "sum": sum}[fname](vals)
            except Exception:  # noqa: BLE001
                return UNKNOWN
        if fname == "range":
            if all(isinstance(a, int) for a in flat_args) and flat_args:
                r = range(*flat_args)
                return r if len(r) <= _MAX_LOOP else UNKNOWN
            return UNKNOWN
        if fname == "list":
            v = flat_args[0] if flat_args else []
            return list(v) if isinstance(v, (list, tuple)) else UNKNOWN
        if fname == "tuple":
            v = flat_args[0] if flat_args else ()
            return tuple(v) if isinstance(v, (list, tuple)) else UNKNOWN

        callee = self._eval(node.func, env)
        if isinstance(callee, _SiteBound):
            # pl.pallas_call(...)(*operands): record the runtime inputs
            callee.site.inputs = flat_args
            return Arr(shape=None, dtype=None)
        if isinstance(callee, FnV):
            return self._call_local(callee.node, flat_args, kwargs)
        if isinstance(callee, RealFn):
            if any(a is UNKNOWN or isinstance(a, (Arr, Lam, FnV))
                   for a in flat_args) or any(
                    v is UNKNOWN or isinstance(v, (Arr, Lam, FnV))
                    for v in kwargs.values()):
                return UNKNOWN
            try:
                return callee.fn(*flat_args, **kwargs)
            except Exception:  # noqa: BLE001 - helper rejected the binding
                raise _Infeasible()
        # array-producing jnp/jax calls and everything else
        if fname.startswith(("jnp.", "jax.", "lax.")):
            return Arr(shape=None, dtype=None)
        return UNKNOWN

    def _call_local(self, fn: ast.FunctionDef, args: list, kwargs: dict):
        env: dict = {}
        fargs = fn.args
        pos = list(fargs.posonlyargs) + list(fargs.args)
        defaults = list(fargs.defaults)
        for i, p in enumerate(pos):
            if i < len(args):
                env[p.arg] = args[i]
            elif p.arg in kwargs:
                env[p.arg] = kwargs[p.arg]
            else:
                di = i - (len(pos) - len(defaults))
                env[p.arg] = (self._eval(defaults[di], {})
                              if 0 <= di < len(defaults) else UNKNOWN)
        for p, d in zip(fargs.kwonlyargs, fargs.kw_defaults):
            env[p.arg] = kwargs.get(
                p.arg, self._eval(d, {}) if d is not None else UNKNOWN)
        try:
            self._exec(fn.body, env)
        except _Return as r:
            return r.value
        return None

    def _eval_pad(self, node: ast.Call, args: list, env: dict):
        if len(args) < 2 or not isinstance(args[0], Arr):
            return Arr(shape=None, dtype=None)
        base, pads = args[0], args[1]
        if base.shape is None or not isinstance(pads, (tuple, list)):
            return Arr(shape=None, dtype=base.dtype)
        if all(isinstance(p, int) for p in pads) and len(pads) == 2:
            pads = [pads]                       # 1-D form
        shape = []
        for dim, p in zip(base.shape, pads):
            d = self.binding.get(dim, dim) if isinstance(dim, str) else dim
            if isinstance(d, int) and isinstance(p, (tuple, list)) \
                    and len(p) == 2 and all(isinstance(x, int) for x in p):
                shape.append(d + p[0] + p[1])
            else:
                shape.append(UNKNOWN)
        if len(shape) != len(base.shape):
            return Arr(shape=None, dtype=base.dtype)
        return Arr(shape=shape, dtype=base.dtype)

    def _eval_site(self, node: ast.Call, args: list, kwargs: dict):
        kernel = args[0] if args else UNKNOWN
        gs = kwargs.get("grid_spec")
        if isinstance(gs, GridSpecV):
            grid = gs.grid
            in_specs, out_specs = gs.in_specs, gs.out_specs
            scratch = gs.scratch
            prefetch = gs.num_scalar_prefetch
        else:
            grid = kwargs.get("grid") or ()
            in_specs = kwargs.get("in_specs") or []
            out_specs = kwargs.get("out_specs") or []
            scratch = list(kwargs.get("scratch_shapes") or [])
            prefetch = 0
        if isinstance(grid, int):
            grid = (grid,)
        out_shape = kwargs.get("out_shape")
        out_shapes = (list(out_shape) if isinstance(out_shape, (list, tuple))
                      else [out_shape] if isinstance(out_shape, SDSV) else [])
        if isinstance(out_specs, BlockV):
            out_specs = [out_specs]
        if isinstance(in_specs, BlockV):
            in_specs = [in_specs]
        se = SiteEval(
            binding=dict(self.binding), kernel=kernel,
            grid=tuple(grid) if isinstance(grid, (tuple, list)) else (),
            in_specs=list(in_specs) if isinstance(in_specs, (list, tuple))
            else [],
            out_specs=list(out_specs) if isinstance(out_specs, (list, tuple))
            else [],
            out_shapes=out_shapes, scratch=scratch, inputs=[],
            num_prefetch=prefetch,
        )
        self.sites[node] = se
        return _SiteBound(se)


@dataclasses.dataclass
class _SiteBound:
    """The value of ``pl.pallas_call(...)`` — calling it records the
    runtime operands on the SiteEval."""

    site: SiteEval


def _same(a, b) -> bool:
    if a is b:
        return True
    if isinstance(a, (int, str, bool, float)) and \
            isinstance(b, (int, str, bool, float)):
        return a == b
    return False


# ---------------------------------------------------------------------------
# kernel-side models
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class RefInfo:
    """One positional ref of the kernel callable: its role in the
    pallas_call signature and the dtype/block the site declares."""

    kind: str                       # "prefetch" | "in" | "out" | "scratch"
    index: int
    dtype: Optional[str]
    block: Optional[tuple]


_LOW_PRECISION = {"bfloat16", "float16", "int8", "uint8", "int16"}


def _iter_stmts(body):
    for s in body:
        yield s
        for attr in ("body", "orelse", "finalbody"):
            sub = getattr(s, attr, None)
            if sub:
                yield from _iter_stmts(sub)


def _fmt_binding(binding: dict, limit: int = 7) -> str:
    items = [f"{k}={v}" for k, v in sorted(binding.items())
             if isinstance(v, (int, str, bool)) and not k.endswith("_shape")]
    out = ", ".join(items[:limit])
    if len(items) > limit:
        out += ", ..."
    return out or "literal shapes"


def _shape_ints(shape) -> Optional[tuple]:
    if shape is None:
        return None
    out = []
    for d in shape:
        if isinstance(d, bool) or not isinstance(d, int):
            return None
        out.append(int(d))
    return tuple(out)


def _dtype_name(v) -> Optional[str]:
    if isinstance(v, str):
        return v
    return None


# ---------------------------------------------------------------------------
# the verifier
# ---------------------------------------------------------------------------


class FileKernelVerifier:
    def __init__(self, path: str, source: str,
                 rules: Optional[Set[str]] = None):
        self.path = path
        self.source = source
        self.rules = rules
        self.tree = ast.parse(source, filename=path)
        self.findings: List[Finding] = []
        self._emitted: Set[tuple] = set()
        self.module_name = self._module_name(path)
        # spec Call nodes covered by a site whose geometry the engine
        # fully resolved — exempt from the literal fallback screen
        self._resolved_spec_nodes: Set[ast.Call] = set()
        self._site_parents: Dict[ast.Call, ast.FunctionDef] = {}
        self.report: Dict[str, object] = {"sites": 0, "resolved": 0}

    @staticmethod
    def _module_name(path: str) -> Optional[str]:
        parts = Path(path).parts
        if "raft_tpu" not in parts:
            return None
        i = len(parts) - 1 - parts[::-1].index("raft_tpu")
        mod = list(parts[i:])
        if not mod[-1].endswith(".py"):
            return None
        mod[-1] = mod[-1][:-3]
        if mod[-1] == "__init__":
            mod.pop()
        return ".".join(mod)

    # -- plumbing ----------------------------------------------------------

    def _emit(self, rule: str, line: int, key: tuple, message: str) -> None:
        if self.rules is not None and rule not in self.rules:
            return
        dedup = (rule, line) + key
        if dedup in self._emitted:
            return
        self._emitted.add(dedup)
        self.findings.append(Finding(rule, self.path, line, message,
                                     engine="kern"))

    def run(self) -> List[Finding]:
        self._find_sites()
        fns: Dict[ast.FunctionDef, List[ast.Call]] = {}
        for call, fn in self._site_parents.items():
            fns.setdefault(fn, []).append(call)
        for fn, calls in fns.items():
            self._verify_function(fn, calls)
        self._literal_screen()
        sup = scan_suppressions(self.source)
        return apply_suppressions(self.findings, sup, self.path)

    def _find_sites(self) -> None:
        stack: List[ast.FunctionDef] = []

        def walk(node):
            is_fn = isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
            if is_fn:
                stack.append(node)
            if isinstance(node, ast.Call) and \
                    (_dotted(node.func) or "") in _PALLAS_CALL_NAMES and stack:
                self._site_parents[node] = stack[-1]
            for child in ast.iter_child_nodes(node):
                walk(child)
            if is_fn:
                stack.pop()

        walk(self.tree)
        self.report["sites"] = len(self._site_parents)

    # -- bindings ----------------------------------------------------------

    def _bindings_for(self, fn: ast.FunctionDef) -> List[Tuple[dict, dict]]:
        """(binding, arrays) pairs to evaluate ``fn`` under: the bare
        literal binding first, then bindings lifted from the function's
        own intra-module call sites (computed shapes flow in from the
        caller — the class the literal heuristic could never see), then
        every matching contract's static cases augmented with tuning
        tile candidates."""
        out: List[Tuple[dict, dict]] = [({}, {})]
        out += [(b, {}) for b in self._callsite_bindings(fn)]
        names_in_fn = {n.id for n in ast.walk(fn) if isinstance(n, ast.Name)}
        aug = self._augmentation_domains(names_in_fn)
        for c in _module_contracts(self.module_name):
            arrays = dict(c.arrays)
            for case in static_cases(c):
                missing = {k: v for k, v in aug.items() if k not in case}
                for combo in _corner_product(missing):
                    b = dict(case)
                    b.update(combo)
                    out.append((b, arrays))
                    if len(out) >= _MAX_BINDINGS:
                        return out
        if len(out) == 1:
            # uncontracted site: fall back to the generic dim table
            for combo in _corner_product(aug, full_first=True):
                if combo:
                    out.append((combo, {}))
                if len(out) >= 32:
                    break
        return out

    def _callsite_bindings(self, fn: ast.FunctionDef) -> List[dict]:
        """Concrete bindings lifted from intra-module calls of ``fn``:
        literal/computable ints, strings, bools, and literal-shaped
        arrays (``jnp.zeros((300, 128))``) flow into the parameters."""
        params = [a.arg for a in fn.args.posonlyargs + fn.args.args]
        bindings: List[dict] = []
        seen: Set[tuple] = set()
        for node in ast.walk(self.tree):
            if not (isinstance(node, ast.Call) and
                    isinstance(node.func, ast.Name) and
                    node.func.id == fn.name):
                continue
            interp = Interp(self.tree, self.module_name)
            interp.fuel = _MAX_STEPS
            b: dict = {}
            try:
                for i, a in enumerate(node.args[:len(params)]):
                    v = interp._eval(a, {})
                    if isinstance(v, (int, str, bool, Arr)) or v is None:
                        b[params[i]] = v
                for kw in node.keywords:
                    if kw.arg:
                        v = interp._eval(kw.value, {})
                        if isinstance(v, (int, str, bool, Arr)) or v is None:
                            b[kw.arg] = v
            except (_Infeasible, _OutOfFuel):
                continue
            if not b:
                continue
            key = tuple(sorted((k, repr(v)) for k, v in b.items()))
            if key not in seen:
                seen.add(key)
                bindings.append(b)
            if len(bindings) >= 8:
                break
        return bindings

    def _augmentation_domains(self, names: Set[str]) -> Dict[str, tuple]:
        domains: Dict[str, tuple] = {}
        try:
            from raft_tpu import tuning

            for k, v in tuning.kernel_shape_candidates().items():
                if k in names:
                    domains[k] = tuple(v)
        except Exception:  # noqa: BLE001 - tuning unavailable: defaults only
            pass
        for k, v in _DEFAULT_DIMS.items():
            if k in names and k not in domains:
                domains[k] = v
        return domains

    # -- per-function verification ----------------------------------------

    def _verify_function(self, fn: ast.FunctionDef,
                         calls: List[ast.Call]) -> None:
        resolved: Set[ast.Call] = set()
        for binding, arrays in self._bindings_for(fn):
            interp = Interp(self.tree, self.module_name)
            try:
                interp.run_function(fn, binding, arrays)
            except (_Infeasible, _OutOfFuel):
                continue
            except RecursionError:
                continue
            for call in calls:
                se = interp.sites.get(call)
                if se is None:
                    continue
                if self._check_site(call, se, interp):
                    resolved.add(call)
                    self._mark_resolved(se)
        self.report["resolved"] = self.report.get("resolved", 0) + \
            len(resolved)

    def _mark_resolved(self, se: SiteEval) -> None:
        """Exempt from the literal fallback screen exactly the spec
        nodes this resolved evaluation CHECKED (BlockV/ScratchV carry
        their Call node) — never the whole enclosing function: a
        literal spec the interpreter never reached (dead branch,
        poisoned loop) must still hit the literal screen."""
        for sp in list(se.in_specs) + list(se.out_specs) + list(se.scratch):
            node = getattr(sp, "node", None)
            if node is not None:
                self._resolved_spec_nodes.add(node)

    # -- site checks -------------------------------------------------------

    def _check_site(self, call: ast.Call, se: SiteEval,
                    interp: Interp) -> bool:
        """Run every rule the binding resolves; returns True when the
        site's geometry was fully concrete (VMEM accounting complete)."""
        grid = se.grid
        grid_ints = all(isinstance(g, int) and not isinstance(g, bool)
                        for g in grid)
        specs: List[Tuple[str, int, object]] = []   # (role, idx, spec)
        for i, sp in enumerate(se.in_specs):
            specs.append(("in", i, sp))
        for i, sp in enumerate(se.out_specs):
            specs.append(("out", i, sp))
        for i, sp in enumerate(se.scratch):
            specs.append(("scratch", i, sp))

        witness = _fmt_binding(se.binding)
        operands = se.inputs[se.num_prefetch:] if se.inputs else []

        total_bytes = 0
        complete = grid_ints and bool(se.out_shapes)
        for role, i, sp in specs:
            block, dtype, arr_shape, line = self._spec_facts(
                role, i, sp, se, operands)
            bl = _shape_ints(block)
            if bl is None:
                complete = False
                continue
            itemsize = dtype_itemsize(dtype) if dtype else 4
            nelem = 1
            for d in bl:
                nelem *= max(int(d), 1)
            total_bytes += nelem * itemsize
            self._check_alignment(role, i, bl, dtype, arr_shape, line,
                                  witness)
            if grid_ints and isinstance(sp, BlockV):
                self._check_bounds(role, i, sp, bl, arr_shape, grid,
                                   interp, line, witness)
        if complete and total_bytes > _VMEM_BUDGET_BYTES:
            self._emit(
                "GL006", call.lineno, ("vmem",),
                f"pallas_call blocks + scratch total "
                f"~{total_bytes / 2**20:.1f} MiB, over the "
                f"~{_VMEM_BUDGET_BYTES // 2**20} MiB per-core VMEM budget "
                f"(witness: {witness})")

        kfn, statics = self._kernel_fn(se, interp)
        if grid_ints:
            self._check_tails(call, se, kfn, statics, interp, witness)
        if kfn is not None:
            kenv = self._kernel_env(kfn, statics, se, operands, interp)
            self._check_grid_hazards(call, se, kfn, kenv, interp, witness)
            self._check_dots(kfn, kenv, interp)
        return complete

    def _spec_facts(self, role: str, i: int, sp, se: SiteEval,
                    operands: list):
        """(block_shape, dtype, array_shape, lineno) for one spec."""
        if isinstance(sp, ScratchV):
            return sp.shape, _dtype_name(sp.dtype), None, sp.lineno
        if not isinstance(sp, BlockV):
            return None, None, None, 0
        arr_shape = None
        dtype = None
        if role == "in" and i < len(operands):
            op = operands[i]
            if isinstance(op, Arr):
                dtype = _dtype_name(op.dtype)
                if op.shape is not None:
                    resolved = [se.binding.get(d, d) if isinstance(d, str)
                                else d for d in op.shape]
                    arr_shape = _shape_ints(resolved)
        elif role == "out" and i < len(se.out_shapes):
            sds = se.out_shapes[i]
            if isinstance(sds, SDSV):
                dtype = _dtype_name(sds.dtype)
                arr_shape = _shape_ints(sds.shape)
        block = sp.shape
        if block is None and arr_shape is not None:
            block = arr_shape          # whole-array spec
        elif block is not None:
            # a None block dim is squeezed: one element of that axis
            block = tuple(1 if d is None else d for d in block)
        return block, dtype, arr_shape, sp.lineno

    def _check_alignment(self, role: str, i: int, block: tuple,
                         dtype: Optional[str], arr_shape: Optional[tuple],
                         line: int, witness: str) -> None:
        if not block:
            return
        itemsize = dtype_itemsize(dtype) if dtype else 4
        sub = SUBLANE_BY_ITEMSIZE[itemsize]
        dt = dtype or "f32-assumed"
        checks = [(len(block) - 1, LANE, "lane")]
        if len(block) >= 2:
            checks.append((len(block) - 2, sub, "sublane"))
        for dim, mult, kind in checks:
            v = block[dim]
            if v == 1 or v % mult == 0:
                continue
            if arr_shape is not None and dim < len(arr_shape) and \
                    arr_shape[dim] == v:
                continue               # block == array dim: always legal
            self._emit(
                "GL016", line, (role, i, kind),
                f"{role}-spec {i} block dim {dim} = {v} is off the "
                f"({sub}, {LANE}) tile for dtype {dt} ({kind} axis): "
                f"not 1, not a multiple of {mult}, and not the array "
                f"dim — forces a relayout or fails to lower "
                f"(witness: {witness})")

    def _check_bounds(self, role: str, i: int, sp: BlockV, block: tuple,
                      arr_shape: Optional[tuple], grid: tuple,
                      interp: Interp, line: int, witness: str) -> None:
        if arr_shape is None or sp.index_map is None or not grid:
            return
        corners = itertools.product(*[(0, int(g) - 1) for g in grid])
        max_idx: List[Optional[int]] = [None] * len(block)
        for corner in itertools.islice(corners, 64):
            res = self._eval_index_map(sp.index_map, corner, interp)
            if res is None:
                return                  # data-dependent map: dynamic job
            for d, v in enumerate(res[:len(block)]):
                if isinstance(v, int) and not isinstance(v, bool):
                    cur = max_idx[d]
                    max_idx[d] = v if cur is None else max(cur, v)
        for d in range(min(len(block), len(arr_shape))):
            if max_idx[d] is None:
                continue
            reach = (max_idx[d] + 1) * block[d]
            if reach > arr_shape[d]:
                self._emit(
                    "GL015", line, ("oob", role, i, d),
                    f"{role}-spec {i} index map reaches block "
                    f"{max_idx[d]} on dim {d}: elements up to {reach} "
                    f"but the array dim is {arr_shape[d]} — out-of-"
                    f"bounds read/write (witness: {witness})")

    def _eval_index_map(self, lam: Lam, corner: tuple,
                        interp: Interp) -> Optional[tuple]:
        params = [a.arg for a in lam.node.args.args]
        env = dict(lam.env)
        for j, p in enumerate(params):
            env[p] = corner[j] if j < len(corner) else UNKNOWN
        interp.fuel = max(interp.fuel, 500)
        try:
            res = interp._eval(lam.node.body, env)
        except (_Infeasible, _OutOfFuel):
            return None
        if isinstance(res, int) and not isinstance(res, bool):
            res = (res,)
        if not isinstance(res, tuple):
            return None
        if any(not isinstance(v, int) or isinstance(v, bool) for v in res):
            return None
        return res

    # -- tails -------------------------------------------------------------

    def _check_tails(self, call: ast.Call, se: SiteEval,
                     kfn: Optional[ast.FunctionDef], statics: dict,
                     interp: Interp, witness: str) -> None:
        for g, ext in enumerate(se.grid):
            if not isinstance(ext, IntV) or not ext.tail:
                continue
            rem = ext.num % ext.den if ext.den else 0
            if ext.kind == "floor":
                self._emit(
                    "GL015", call.lineno, ("floor", g),
                    f"grid dim {g} extent is {ext.num} // {ext.den} with "
                    f"remainder {rem}: the array's last {rem} elements on "
                    f"that axis are never visited by the grid "
                    f"(witness: {witness})")
            elif ext.kind == "ceil":
                if kfn is not None and self._has_mask_evidence(kfn, interp):
                    continue
                kname = kfn.name if kfn is not None else "<unresolved>"
                self._emit(
                    "GL015", call.lineno, ("tail", g),
                    f"grid dim {g} extent is ceil({ext.num} / {ext.den}) "
                    f"with {ext.num} % {ext.den} = {rem}: the tail tile is "
                    f"reachable but kernel {kname}() shows no tail mask "
                    f"(no jnp.where/pl.when guarded by a bound compare) — "
                    f"pad garbage can win the reduction "
                    f"(witness: {witness})")

    _IDX_CALLS = ("broadcasted_iota", "iota", "program_id")

    def _has_mask_evidence(self, kfn: ast.FunctionDef,
                           interp: Interp) -> bool:
        """A tail mask must gate on an INDEX-derived value (iota /
        program_id, or a name computed from one) — a numeric clamp like
        ``where(dist < 0, 0, dist)`` has an inequality but masks
        nothing positional, so it is not evidence."""
        bodies = [kfn]
        called = {(_dotted(sub.func) or "").rsplit(".", 1)[-1]
                  for sub in ast.walk(kfn) if isinstance(sub, ast.Call)}
        for name in called:
            if name in interp.fns:
                bodies.append(interp.fns[name])

        idx_names: Set[str] = set()

        def has_idx(node: ast.AST) -> bool:
            for s in ast.walk(node):
                if isinstance(s, ast.Call) and (
                        _dotted(s.func) or "").rsplit(".", 1)[-1] \
                        in self._IDX_CALLS:
                    return True
                if isinstance(s, ast.Name) and s.id in idx_names:
                    return True
            return False

        # fixed point over assignments: index carriers (col = iota + off)
        # and boolean masks derived from them (valid = col < size)
        for _ in range(4):
            grew = False
            for body in bodies:
                for sub in ast.walk(body):
                    if isinstance(sub, ast.Assign) and \
                            len(sub.targets) == 1 and \
                            isinstance(sub.targets[0], ast.Name) and \
                            has_idx(sub.value):
                        if sub.targets[0].id not in idx_names:
                            idx_names.add(sub.targets[0].id)
                            grew = True
            if not grew:
                break

        for body in bodies:
            for sub in ast.walk(body):
                if not isinstance(sub, ast.Call) or not sub.args:
                    continue
                fname = _dotted(sub.func) or ""
                is_when = fname in ("pl.when", "pltpu.when")
                is_where = fname.rsplit(".", 1)[-1] == "where"
                if not (is_when or is_where):
                    continue
                test = sub.args[0]
                if not has_idx(test):
                    continue
                has_cmp = any(isinstance(c, ast.Compare)
                              for c in ast.walk(test))
                named_mask = isinstance(test, ast.Name) and \
                    test.id in idx_names
                if has_cmp or named_mask:
                    return True
        return False

    # -- kernel resolution -------------------------------------------------

    def _kernel_fn(self, se: SiteEval, interp: Interp
                   ) -> Tuple[Optional[ast.FunctionDef], dict]:
        k = se.kernel
        statics: dict = {}
        if isinstance(k, PartialV):
            statics = {n: v for n, v in k.kwargs.items()}
            k = k.fn
        if isinstance(k, FnV):
            return k.node, statics
        return None, statics

    def _kernel_env(self, kfn: ast.FunctionDef, statics: dict,
                    se: SiteEval, operands: list, interp: Interp) -> dict:
        refs: List[RefInfo] = []
        for i in range(se.num_prefetch):
            refs.append(RefInfo("prefetch", i, "int32", None))
        for i, sp in enumerate(se.in_specs):
            dtype = None
            if i < len(operands) and isinstance(operands[i], Arr):
                dtype = _dtype_name(operands[i].dtype)
            refs.append(RefInfo(
                "in", i, dtype,
                sp.shape if isinstance(sp, BlockV) else None))
        for i, sp in enumerate(se.out_specs):
            dtype = None
            if i < len(se.out_shapes) and isinstance(se.out_shapes[i], SDSV):
                dtype = _dtype_name(se.out_shapes[i].dtype)
            refs.append(RefInfo(
                "out", i, dtype,
                sp.shape if isinstance(sp, BlockV) else None))
        for i, sp in enumerate(se.scratch):
            refs.append(RefInfo(
                "scratch", i,
                _dtype_name(sp.dtype) if isinstance(sp, ScratchV) else None,
                sp.shape if isinstance(sp, ScratchV) else None))

        env: dict = {}
        args = kfn.args
        pos = [a.arg for a in args.posonlyargs + args.args]
        ri = 0
        for name in pos:
            if name in statics:
                env[name] = statics[name]
            elif ri < len(refs):
                env[name] = refs[ri]
                ri += 1
            else:
                env[name] = UNKNOWN
        if args.vararg is not None:
            env[args.vararg.arg] = list(refs[ri:])
        for a in args.kwonlyargs:
            if a.arg in statics:
                env[a.arg] = statics[a.arg]
        interp.fuel = 20000
        interp.binding = se.binding
        try:
            interp._exec(kfn.body, env)
        except (_Return, _Infeasible, _OutOfFuel, RecursionError):
            pass
        return env

    # -- GL017 grid hazards ------------------------------------------------

    def _check_grid_hazards(self, call: ast.Call, se: SiteEval,
                            kfn: ast.FunctionDef, kenv: dict,
                            interp: Interp, witness: str) -> None:
        grid = se.grid
        if not grid or not all(isinstance(g, int) and not isinstance(g, bool)
                               for g in grid):
            return
        revisit_dims_per_out: Dict[int, List[int]] = {}
        for i, sp in enumerate(se.out_specs):
            if not isinstance(sp, BlockV) or sp.index_map is None:
                continue
            params = [a.arg for a in sp.index_map.node.args.args]
            gparams = params[:len(grid)]
            used = {n.id for n in ast.walk(sp.index_map.node.body)
                    if isinstance(n, ast.Name)}
            unused = [g for g, p in enumerate(gparams)
                      if p not in used and grid[g] > 1]
            if unused:
                revisit_dims_per_out[i] = unused
        if not revisit_dims_per_out:
            return

        writes = self._ref_writes(kfn, kenv)
        for i, dims in revisit_dims_per_out.items():
            for node, is_aug, reads_ref, nm in writes:
                ri = kenv.get(nm)
                if not isinstance(ri, RefInfo) or ri.kind != "out" \
                        or ri.index != i:
                    continue
                dim_s = ",".join(str(d) for d in dims)
                if is_aug or reads_ref:
                    if not self._has_init_guard_for(kfn, nm):
                        self._emit(
                            "GL017", node.lineno, ("uninit", i),
                            f"output ref {nm!r} is revisited across grid "
                            f"dim(s) {dim_s} and accumulated into, but the "
                            f"kernel has no first-step init "
                            f"(pl.when/program_id guard): pallas outputs "
                            f"start uninitialized (witness: {witness})")
                else:
                    self._emit(
                        "GL017", node.lineno, ("overwrite", i),
                        f"output ref {nm!r} is plainly overwritten while "
                        f"its index map ignores grid dim(s) {dim_s} "
                        f"(extent > 1): each revisit clobbers the "
                        f"previous step's result — accumulate with a "
                        f"first-step init or index the block by that "
                        f"grid dim (witness: {witness})")

    def _has_init_guard_for(self, kfn: ast.FunctionDef, nm: str) -> bool:
        """First-step-init evidence is PER REF: a ``@pl.when(...)``
        guarded function must write THIS ref — an unrelated guard (or
        another output's init) must not launder an uninitialized
        accumulator."""
        for sub in ast.walk(kfn):
            if not isinstance(sub, ast.FunctionDef):
                continue
            guarded = any(
                isinstance(deco, ast.Call) and
                (_dotted(deco.func) or "") in ("pl.when", "pltpu.when")
                for deco in sub.decorator_list)
            if not guarded:
                continue
            for w in ast.walk(sub):
                targets = []
                if isinstance(w, ast.Assign):
                    targets = w.targets
                elif isinstance(w, ast.AugAssign):
                    targets = [w.target]
                for t in targets:
                    if isinstance(t, ast.Subscript) and \
                            isinstance(t.value, ast.Name) and \
                            t.value.id == nm:
                        return True
        return False

    def _ref_writes(self, kfn: ast.FunctionDef, kenv: dict
                    ) -> List[tuple]:
        out = []
        for sub in ast.walk(kfn):
            targets = []
            value = None
            if isinstance(sub, ast.Assign):
                targets, value = sub.targets, sub.value
            elif isinstance(sub, ast.AugAssign):
                targets, value = [sub.target], sub.value
            for t in targets:
                if isinstance(t, ast.Subscript) and \
                        isinstance(t.value, ast.Name):
                    nm = t.value.id
                    if not isinstance(kenv.get(nm), RefInfo):
                        continue
                    reads = any(
                        isinstance(n, ast.Name) and n.id == nm
                        for n in ast.walk(value)) if value is not None \
                        else False
                    out.append((sub, isinstance(sub, ast.AugAssign),
                                reads, nm))
        return out

    # -- GL018 MXU dtype audit ---------------------------------------------

    def _check_dots(self, kfn: ast.FunctionDef, kenv: dict,
                    interp: Interp) -> None:
        dtenv: Dict[str, Optional[str]] = {}
        for name, v in kenv.items():
            if isinstance(v, RefInfo):
                dtenv[name] = v.dtype
            elif isinstance(v, Arr):
                dtenv[name] = _dtype_name(v.dtype)
            elif isinstance(v, str) and v in _DTYPE_NAMES.values():
                dtenv[name] = v
        for stmt in _iter_stmts(kfn.body):
            if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1 and \
                    isinstance(stmt.targets[0], ast.Name):
                dtenv[stmt.targets[0].id] = self._expr_dtype(
                    stmt.value, dtenv)
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Call) and \
                        (_dotted(sub.func) or "") in _DOT_NAMES and \
                        len(sub.args) >= 2:
                    self._check_one_dot(sub, dtenv)

    def _check_one_dot(self, node: ast.Call, dtenv: dict) -> None:
        a = self._expr_dtype(node.args[0], dtenv)
        b = self._expr_dtype(node.args[1], dtenv)
        preferred = any(kw.arg == "preferred_element_type"
                        for kw in node.keywords)
        fname = _dotted(node.func)
        if a and b and a != b:
            self._emit(
                "GL018", node.lineno, ("mismatch",),
                f"{fname}() operand dtypes differ ({a} vs {b}): the "
                f"contraction silently promotes off the MXU's native "
                f"pass — cast both operands to one matmul dtype")
        elif not preferred and ((a in _LOW_PRECISION) or
                                (b in _LOW_PRECISION)):
            self._emit(
                "GL018", node.lineno, ("accum",),
                f"{fname}() on {a or b} operands without "
                f"preferred_element_type: the accumulator stays "
                f"low-precision — pass preferred_element_type="
                f"jnp.float32 to accumulate in f32 on the MXU")

    def _expr_dtype(self, node: ast.AST, dtenv: dict) -> Optional[str]:
        if isinstance(node, ast.Name):
            return dtenv.get(node.id)
        if isinstance(node, ast.Subscript):
            return self._expr_dtype(node.value, dtenv)
        if isinstance(node, ast.Attribute):
            d = _dotted(node)
            if d in _DTYPE_NAMES:
                return _DTYPE_NAMES[d]
            if node.attr == "dtype":
                return self._expr_dtype(node.value, dtenv)
            return None
        if isinstance(node, ast.Call):
            fname = _dotted(node.func) or ""
            if isinstance(node.func, ast.Attribute) and \
                    node.func.attr == "astype" and node.args:
                arg = node.args[0]
                d = _dotted(arg)
                if d in _DTYPE_NAMES:
                    return _DTYPE_NAMES[d]
                if isinstance(arg, ast.Constant) and isinstance(
                        arg.value, str):
                    return arg.value
                if isinstance(arg, ast.Attribute) and arg.attr == "dtype":
                    return self._expr_dtype(arg.value, dtenv)
                return None
            if fname in _DOT_NAMES:
                for kw in node.keywords:
                    if kw.arg == "preferred_element_type":
                        d = _dotted(kw.value)
                        return _DTYPE_NAMES.get(d or "", None)
                return None
            if fname in ("jnp.zeros", "jnp.ones", "jnp.full", "jnp.empty"):
                for kw in node.keywords:
                    if kw.arg == "dtype":
                        return _DTYPE_NAMES.get(_dotted(kw.value) or "")
                for arg in node.args[1:]:
                    d = _DTYPE_NAMES.get(_dotted(arg) or "")
                    if d:
                        return d
                return None
            if fname.rsplit(".", 1)[-1] == "where" and len(node.args) >= 3:
                a = self._expr_dtype(node.args[1], dtenv)
                b = self._expr_dtype(node.args[2], dtenv)
                return a if a == b else None
            return None
        if isinstance(node, ast.BinOp):
            a = self._expr_dtype(node.left, dtenv)
            b = self._expr_dtype(node.right, dtenv)
            if a and b:
                return a if a == b else None
            return a or b
        return None

    # -- literal fallback screen (retired GL006 heuristic) -----------------

    def _literal_screen(self) -> None:
        """The pre-engine literal heuristic, kept only for spec calls
        the evaluator could not resolve: off-tile literal dims and
        per-function literal VMEM totals (GL006)."""
        fn_totals: Dict[ast.FunctionDef, List[int]] = {}
        stack: List[ast.FunctionDef] = []

        def walk(node):
            is_fn = isinstance(node, ast.FunctionDef)
            if is_fn:
                stack.append(node)
            if isinstance(node, ast.Call):
                fname = _dotted(node.func) or ""
                if fname in _BLOCKSPEC_NAMES + _VMEM_SCRATCH_NAMES and \
                        node.args and node not in self._resolved_spec_nodes:
                    dims = _const_int_tuple(node.args[0])
                    if dims is not None:
                        kind = ("BlockSpec" if fname in _BLOCKSPEC_NAMES
                                else "VMEM scratch")
                        self._literal_spec(node, dims, kind)
                        if stack and all(d is not None for d in dims):
                            n = 1
                            for d in dims:
                                n *= d
                            fn_totals.setdefault(stack[-1], []).append(4 * n)
            for child in ast.iter_child_nodes(node):
                walk(child)
            if is_fn:
                stack.pop()

        walk(self.tree)
        for fn, sizes in fn_totals.items():
            total = sum(sizes)
            if total > _VMEM_BUDGET_BYTES:
                self._emit(
                    "GL006", fn.lineno, ("literal-vmem", fn.name),
                    f"{len(sizes)} literal BlockSpec/VMEM blocks in "
                    f"{fn.name}() total ~{total / 2**20:.1f} MiB, over "
                    f"the ~{_VMEM_BUDGET_BYTES // 2**20} MiB VMEM budget")

    def _literal_spec(self, node: ast.Call, dims: list, kind: str) -> None:
        last = dims[-1]
        if last is not None and last != 1 and last % LANE != 0:
            self._emit(
                "GL006", node.lineno, ("literal-lane",),
                f"{kind} trailing dim {last} is not a multiple of "
                f"{LANE} (TPU lane width): forces relayout")
        if len(dims) >= 2:
            sub = dims[-2]
            if sub is not None and sub != 1 and sub % 8 != 0:
                self._emit(
                    "GL006", node.lineno, ("literal-sublane",),
                    f"{kind} sublane dim {sub} is not a multiple of 8 "
                    f"(f32 tile; bf16 needs 16, int8 32): forces relayout")


def _const_int_tuple(node: ast.AST) -> Optional[List[Optional[int]]]:
    if not isinstance(node, (ast.Tuple, ast.List)):
        return None
    out: List[Optional[int]] = []
    for el in node.elts:
        if isinstance(el, ast.Constant) and isinstance(el.value, int):
            out.append(el.value)
        else:
            out.append(None)
    return out


def _corner_product(domains: Dict[str, tuple],
                    full_first: bool = False) -> List[dict]:
    """Bounded cartesian product over candidate domains: first/last of
    each tuple (the geometry corners) plus, when ``full_first``, the
    full first-choice binding."""
    if not domains:
        return [{}]
    corners = {k: tuple(dict.fromkeys((v[0], v[-1])))
               for k, v in domains.items() if v}
    keys = sorted(corners)
    out = []
    if full_first:
        out.append({k: domains[k][0] for k in keys})
    for combo in itertools.product(*[corners[k] for k in keys]):
        out.append(dict(zip(keys, combo)))
        if len(out) >= 64:
            break
    return [dict(t) for t in dict.fromkeys(
        tuple(sorted(c.items())) for c in out)]


# ---------------------------------------------------------------------------
# contract loading
# ---------------------------------------------------------------------------

_CONTRACTS_STATE = {"loaded": False}


def _module_contracts(module_name: Optional[str]):
    if module_name is None:
        return []
    from raft_tpu.analysis import contracts as _c

    if not _CONTRACTS_STATE["loaded"]:
        try:
            _c.load_all()
        except Exception:  # noqa: BLE001 - heavy deps missing: lint without contracts
            pass
        _CONTRACTS_STATE["loaded"] = True
    return _c.contracts_for_module(module_name)


# ---------------------------------------------------------------------------
# public API (mirrors analysis.lint / analysis.races)
# ---------------------------------------------------------------------------


def lint_source(source: str, path: str = "<string>",
                rules: Optional[Set[str]] = None) -> List[Finding]:
    return FileKernelVerifier(path, source, rules).run()


def lint_file(path, rules: Optional[Set[str]] = None) -> List[Finding]:
    p = Path(path)
    try:
        source = p.read_text()
    except (OSError, UnicodeDecodeError) as e:
        return [Finding("GL000", str(p), 0, f"unreadable: {e}",
                        engine="kern")]
    try:
        return lint_source(source, str(p), rules)
    except SyntaxError as e:
        return [Finding("GL000", str(p), e.lineno or 0,
                        f"syntax error: {e.msg}", engine="kern")]


def lint_paths(paths: Sequence, rules: Optional[Set[str]] = None
               ) -> List[Finding]:
    """Kernel-verify files and directories (``**/*.py``, no __pycache__)."""
    findings: List[Finding] = []
    for raw in paths:
        p = Path(raw)
        if p.is_dir():
            files = sorted(
                f for f in p.rglob("*.py") if "__pycache__" not in f.parts
            )
        else:
            files = [p]
        for f in files:
            findings.extend(lint_file(f, rules))
    return findings
