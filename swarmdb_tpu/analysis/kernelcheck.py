"""swarmlint kernel family (SWL901-905): static Pallas kernel verification.

Parses every ``pl.pallas_call`` site (grid, BlockSpecs, index maps,
scalar-prefetch operands, scratch shapes) and symbolically evaluates the
index maps over the grid with interval/affine arithmetic. Stdlib-only like
every swarmlint family — the CI lint job runs without JAX installed, so
nothing here imports jax; the *source* of the kernels is the input.

Rules:

SWL901 out-of-bounds block
    ``index_map(g) * block_shape + block_shape`` can exceed the operand
    extent on some grid coordinate (or the block index can go negative).
    Both directions need a PROOF: the checker stays quiet when neither
    safety nor violation is provable (symbolic dims it cannot relate), and
    it skips any axis whose index expression depends on scalar-prefetch
    DATA (page tables, row descriptors) — those bounds are the runtime
    sanitizer's job (obs/kerncheck.py bounds-checked refs).

SWL902 grid write race
    The output block index map ignores a non-innermost grid axis, so two
    grid coordinates map to the same output block. On TPU the grid runs
    sequentially so a deliberate accumulate/finalize revisit is legal —
    the ``# swarmlint: revisit[<dim>]`` directive (grammar-registered in
    core.py) sanctions it; an *undeclared* revisit is how a kernel
    silently keeps only the last grid step's contribution. Ignoring the
    innermost axis is the standard sequential-accumulation idiom and is
    always allowed.

SWL903 VMEM budget
    Per-grid-step block footprint — double-buffered in/out blocks (Pallas
    pipelines the copies, so every non-SMEM block counts twice) plus VMEM
    scratch — against the per-platform VMEM table below (shared with
    swarmprof's platform detection: obs/profiler.py delegates here so the
    two subsystems can never disagree on the budget). Warn at 80%, error
    past 100%. Fires only on a fully concrete footprint; symbolic
    footprints are exported as estimate formulas instead
    (:func:`estimate_vmem`) and folded into the ``/admin/profile``
    variant table at trace time.

SWL904 tiling misalignment
    Concrete block minor dims that are not multiples of the dtype's
    sublane x lane tile — (8,128) f32, (16,128) bf16, (32,128) int8. A
    misaligned block still runs, at a fraction of the VPU/MXU duty cycle;
    the int8 row is exactly what the quantized-KV sprint needs policed.

SWL905 unwritten output
    No store to an output ref is reachable on some grid cell: either the
    kernel never stores to the ref at all, or every store sits under a
    ``@pl.when`` guard that is provably unsatisfiable over the grid.
    Stores under data-dependent guards count as coverage here (static
    analysis cannot decide them) — the runtime canary in obs/kerncheck.py
    owns that half of the contract.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

from .core import Finding, SourceFile, dotted_name, make_finding

# --------------------------------------------------------------------- VMEM
# Per-platform VMEM budgets (bytes/core). Substring-matched against the
# normalized device kind exactly like obs/profiler._PLATFORM_PEAKS — the
# profiler imports THIS table (not the other way round: analysis/ must stay
# importable in the JAX-less CI lint job). v2-v5 carry ~16 MiB of VMEM per
# core; Trillium (v6) doubles it. SWARMDB_VMEM_BYTES overrides everything.

PLATFORM_VMEM_BYTES: Tuple[Tuple[str, int], ...] = (
    ("v6", 32 * 2 ** 20),
    ("v5p", 16 * 2 ** 20),
    ("v5e", 16 * 2 ** 20),
    ("v5", 16 * 2 ** 20),
    ("v4", 16 * 2 ** 20),
    ("v3", 16 * 2 ** 20),
    ("v2", 16 * 2 ** 20),
)

DEFAULT_VMEM_BYTES = 16 * 2 ** 20


def vmem_budget(device_kind: str = "") -> int:
    """VMEM budget in bytes for a device kind ('' = conservative default).

    Matching mirrors swarmprof's platform detection: lowercase, strip
    spaces and the 'tpu' prefix, then first substring hit wins."""
    env = os.environ.get("SWARMDB_VMEM_BYTES", "")
    if env:
        try:
            return int(env)
        except ValueError:
            pass
    kind = (device_kind or "").lower().replace(" ", "").replace("tpu", "")
    for sub, budget in PLATFORM_VMEM_BYTES:
        if sub in kind:
            return budget
    return DEFAULT_VMEM_BYTES


# Element sizes for dtypes spelled in source; dtype-polymorphic operands
# (``q.dtype``) fall back to 4 bytes — an upper bound for every dtype the
# serving engine ships (f32 accumulate, bf16 stream), so the SWL903 error
# direction never under-counts.
_DTYPE_BYTES = {
    "float32": 4, "f32": 4, "int32": 4, "uint32": 4,
    "bfloat16": 2, "float16": 2, "int16": 2, "uint16": 2,
    "int8": 1, "uint8": 1, "float8_e4m3fn": 1, "float8_e5m2": 1,
    "int64": 8, "float64": 8,
}

# Minimum sublane count for the minor-most-but-one dim, per element size
# (lane dim is always 128): (8,128) f32, (16,128) bf16, (32,128) int8.
_SUBLANE = {4: 8, 2: 16, 1: 32, 8: 8}
_LANE = 128


# ------------------------------------------------------------- expressions
#
# Symbolic values are nested tuples (hashable -> usable as affine atoms
# with syntactic cancellation):
#   ("const", n)          literal int
#   ("dim", name)         a dimension taken off an array .shape (lb 1)
#   ("sym", name)         any other name (unknown bounds)
#   ("grid", i)           the i-th grid coordinate, 0 <= g_i < grid[i]
#   ("data",)             scalar-prefetch dependent (page tables, rows)
#   ("add"|"mul"|"floordiv"|"mod"|"min"|"max", a, b)
#   ("opaque", text)      anything the evaluator does not model

Expr = Tuple[Any, ...]

_COMPOSITE = ("add", "mul", "floordiv", "mod", "min", "max")


# ``<array>.dtype.itemsize`` and a scratch buffer declared with
# ``<array>.dtype``: one symbol, bound by the dispatcher (`estimate_vmem`)
_ITEMSIZE: Expr = ("sym", "itemsize")


def _c(n: int) -> Expr:
    return ("const", int(n))


def _add(a: Expr, b: Expr) -> Expr:
    if a[0] == "const" and b[0] == "const":
        return _c(a[1] + b[1])
    if a[0] == "const" and a[1] == 0:
        return b
    if b[0] == "const" and b[1] == 0:
        return a
    return ("add", a, b)


def _mul(a: Expr, b: Expr) -> Expr:
    if a[0] == "const" and b[0] == "const":
        return _c(a[1] * b[1])
    if (a[0] == "const" and a[1] == 0) or (b[0] == "const" and b[1] == 0):
        return _c(0)
    if a[0] == "const" and a[1] == 1:
        return b
    if b[0] == "const" and b[1] == 1:
        return a
    return ("mul", a, b)


def _neg(a: Expr) -> Expr:
    return _mul(_c(-1), a)


def _sub(a: Expr, b: Expr) -> Expr:
    return _add(a, _neg(b))


def _floordiv(a: Expr, b: Expr) -> Expr:
    if a[0] == "const" and b[0] == "const" and b[1] != 0:
        return _c(a[1] // b[1])
    return ("floordiv", a, b)


def _mod(a: Expr, b: Expr) -> Expr:
    if a[0] == "const" and b[0] == "const" and b[1] != 0:
        return _c(a[1] % b[1])
    return ("mod", a, b)


def _min(a: Expr, b: Expr) -> Expr:
    if a[0] == "const" and b[0] == "const":
        return _c(min(a[1], b[1]))
    if a == b:
        return a
    return ("min", a, b)


def _max(a: Expr, b: Expr) -> Expr:
    if a[0] == "const" and b[0] == "const":
        return _c(max(a[1], b[1]))
    if a == b:
        return a
    return ("max", a, b)


def _contains(e: Expr, kinds: Tuple[str, ...]) -> bool:
    if e[0] in kinds:
        return True
    if e[0] in _COMPOSITE:
        return _contains(e[1], kinds) or _contains(e[2], kinds)
    return False


def _subst(e: Expr, atom: Expr, repl: Expr) -> Expr:
    if e == atom:
        return repl
    if e[0] in _COMPOSITE:
        a = _subst(e[1], atom, repl)
        b = _subst(e[2], atom, repl)
        ctor = {"add": _add, "mul": _mul, "floordiv": _floordiv,
                "mod": _mod, "min": _min, "max": _max}[e[0]]
        return ctor(a, b)
    return e


def _affine(e: Expr) -> Tuple[int, Dict[Expr, int]]:
    """Normalize to const + sum(coeff * atom); non-affine subtrees become
    atoms keyed by their own (hashable) expression, so two syntactically
    identical opaque terms cancel — sound, since equal expressions over
    equal inputs are equal values."""
    k = e[0]
    if k == "const":
        return e[1], {}
    if k == "add":
        c1, t1 = _affine(e[1])
        c2, t2 = _affine(e[2])
        for atom, co in t2.items():
            t1[atom] = t1.get(atom, 0) + co
        return c1 + c2, {a: co for a, co in t1.items() if co != 0}
    if k == "mul":
        c1, t1 = _affine(e[1])
        c2, t2 = _affine(e[2])
        if not t1:  # scalar * affine
            return c1 * c2, {a: co * c1 for a, co in t2.items() if co * c1}
        if not t2:
            return c1 * c2, {a: co * c2 for a, co in t1.items() if co * c2}
        return 0, {e: 1}
    return 0, {e: 1}


def _rebuild(const: int, terms: Dict[Expr, int]) -> Expr:
    out: Expr = _c(const)
    for atom, co in terms.items():
        out = _add(out, _mul(_c(co), atom))
    return out


def _atom_lb(atom: Expr, depth: int = 0) -> Optional[int]:
    """Provable integer lower bound of an affine atom, or None."""
    if depth > 8:
        return None
    k = atom[0]
    if k == "const":
        return atom[1]
    if k == "dim":
        return 1       # array extents: a 0-sized kernel operand is not a
    if k == "grid":    # shape this checker models (documented contract)
        return 0
    if k in ("floordiv", "mod"):
        la = _expr_lb(atom[1], depth + 1)
        lb = _expr_lb(atom[2], depth + 1)
        if la is not None and la >= 0 and lb is not None and lb >= 1:
            return 0
        return None
    if k == "mul":
        la = _expr_lb(atom[1], depth + 1)
        lb = _expr_lb(atom[2], depth + 1)
        if la is not None and la >= 0 and lb is not None and lb >= 0:
            return la * lb
        return None
    if k == "min":
        la = _expr_lb(atom[1], depth + 1)
        lb = _expr_lb(atom[2], depth + 1)
        if la is not None and lb is not None:
            return min(la, lb)
        return None
    if k == "max":
        la = _expr_lb(atom[1], depth + 1)
        lb = _expr_lb(atom[2], depth + 1)
        cands = [x for x in (la, lb) if x is not None]
        return max(cands) if cands else None
    return None  # sym / data / opaque


def _expr_lb(e: Expr, depth: int = 0) -> Optional[int]:
    """Lower bound of an arbitrary expression via affine + atom bounds."""
    if depth > 8:
        return None
    const, terms = _affine(e)
    total = const
    for atom, co in terms.items():
        lb = _atom_lb(atom, depth + 1)
        if lb is None or co < 0:
            return None
        total += co * lb
    return total


def _prove_nonneg(e: Expr, grid: Sequence[Expr], depth: int = 0,
                  maximize_grid: bool = False) -> bool:
    """Prove ``e >= 0``. With ``maximize_grid=False`` grid coordinates are
    substituted adversarially to MINIMIZE e (a universal safety proof);
    with True they are substituted to MAXIMIZE e (an existence proof of a
    violating coordinate — used only to make a *definite* finding, so
    min/max atoms abort it rather than risk a wrong witness). Returns True
    only on proof; False means "could not prove", never "false"."""
    if depth > 16 or _contains(e, ("data",)):
        return False
    const, terms = _affine(e)
    for atom in terms:
        if atom[0] in ("min", "max"):
            if maximize_grid:
                return False
            # min(a,b) pointwise equals ONE of its arms: if both
            # substitutions are provably nonneg, so is the original.
            return (_prove_nonneg(_subst(e, atom, atom[1]), grid,
                                  depth + 1, maximize_grid)
                    and _prove_nonneg(_subst(e, atom, atom[2]), grid,
                                      depth + 1, maximize_grid))
    for atom, co in terms.items():
        if atom[0] == "grid":
            i = atom[1]
            if i >= len(grid):
                return False
            hi = _sub(grid[i], _c(1))
            if maximize_grid:
                repl = hi if co > 0 else _c(0)
            else:
                repl = _c(0) if co > 0 else hi
            return _prove_nonneg(_subst(e, atom, repl), grid, depth + 1,
                                 maximize_grid)
    total = const
    for atom, co in terms.items():
        lb = _atom_lb(atom)
        if lb is None or co < 0:
            return False
        total += co * lb
    return total >= 0


def _pretty(e: Expr) -> str:
    k = e[0]
    if k == "const":
        return str(e[1])
    if k in ("dim", "sym", "opaque"):
        return str(e[1])
    if k == "grid":
        return f"g{e[1]}"
    if k == "data":
        return "<data>"
    if k == "add":
        return f"({_pretty(e[1])} + {_pretty(e[2])})"
    if k == "mul":
        return f"{_pretty(e[1])}*{_pretty(e[2])}"
    if k == "floordiv":
        return f"({_pretty(e[1])} // {_pretty(e[2])})"
    if k == "mod":
        return f"({_pretty(e[1])} % {_pretty(e[2])})"
    if k in ("min", "max"):
        return f"{k}({_pretty(e[1])}, {_pretty(e[2])})"
    return "?"


def eval_with_dims(e: Expr, dims: Dict[str, int]) -> Optional[int]:
    """Evaluate an exported footprint expression under concrete dim
    bindings (``{"W": 256, "Hq": 32, ...}``); None if any leaf is
    unbound. This is the swarmprof fold-in path: the dispatchers bind the
    trace-time shapes and the result lands in the variant table meta."""
    k = e[0]
    if k == "const":
        return e[1]
    if k in ("dim", "sym", "opaque"):
        v = dims.get(e[1])
        return int(v) if v is not None else None
    if k in _COMPOSITE:
        a = eval_with_dims(e[1], dims)
        b = eval_with_dims(e[2], dims)
        if a is None or b is None:
            return None
        if k == "add":
            return a + b
        if k == "mul":
            return a * b
        if k == "floordiv":
            return a // b if b else None
        if k == "mod":
            return a % b if b else None
        if k == "min":
            return min(a, b)
        return max(a, b)
    return None


# ------------------------------------------------------------- evaluation


class _Env:
    """Symbolic bindings for one wrapper function (or one index-map /
    kernel scope derived from it)."""

    def __init__(self) -> None:
        self.vars: Dict[str, Expr] = {}
        self.ast_vars: Dict[str, ast.expr] = {}   # raw RHS for spec lists
        self.shapes: Dict[str, Dict[int, Expr]] = {}
        self.aliases: Dict[str, str] = {}
        self.data_names: Set[str] = set()
        self.grid_params: Dict[str, int] = {}
        self.grid_sizes: List[Expr] = []
        self.local_fns: Dict[str, ast.FunctionDef] = {}

    def child(self) -> "_Env":
        out = _Env()
        out.vars = dict(self.vars)
        out.ast_vars = dict(self.ast_vars)
        out.shapes = {k: dict(v) for k, v in self.shapes.items()}
        out.aliases = dict(self.aliases)
        out.data_names = set(self.data_names)
        out.grid_sizes = list(self.grid_sizes)
        out.local_fns = dict(self.local_fns)
        return out

    def resolve_alias(self, name: str) -> str:
        seen = set()
        while name in self.aliases and name not in seen:
            seen.add(name)
            name = self.aliases[name]
        return name

    def shape_axis(self, name: str, i: int) -> Expr:
        name = self.resolve_alias(name)
        got = self.shapes.get(name, {}).get(i)
        if got is not None:
            return got
        if name in self.data_names:
            return ("data",)
        return ("dim", f"{name}.shape[{i}]")


class _ModuleInfo:
    def __init__(self, src: SourceFile) -> None:
        self.src = src
        self.functions: Dict[str, ast.FunctionDef] = {
            n.name: n for n in src.tree.body
            if isinstance(n, ast.FunctionDef)
        }
        # module-level ``NAME = <integer expression>`` (a kernel's tile
        # and budget constants), so a footprint that uses one is a number
        self.constants: Dict[str, Expr] = {}
        for n in src.tree.body:
            if (isinstance(n, ast.Assign) and len(n.targets) == 1
                    and isinstance(n.targets[0], ast.Name)):
                v = _eval(n.value, _Env(), self)
                if v[0] == "const":
                    self.constants[n.targets[0].id] = v


_INLINE_DEPTH = 6


def _eval(node: ast.expr, env: _Env, mod: _ModuleInfo,
          depth: int = 0) -> Expr:
    if depth > 24:
        return ("opaque", "<depth>")
    if isinstance(node, ast.Constant):
        if isinstance(node.value, bool):
            return _c(int(node.value))
        if isinstance(node.value, int):
            return _c(node.value)
        return ("opaque", repr(node.value)[:60])
    if isinstance(node, ast.Name):
        if node.id in env.grid_params:
            return ("grid", env.grid_params[node.id])
        if node.id in env.data_names:
            return ("data",)
        if node.id in env.vars:
            return env.vars[node.id]
        if node.id in mod.constants:
            return mod.constants[node.id]
        return ("sym", node.id)
    if isinstance(node, ast.UnaryOp):
        v = _eval(node.operand, env, mod, depth + 1)
        if isinstance(node.op, ast.USub):
            return _neg(v)
        if isinstance(node.op, ast.UAdd):
            return v
        return ("opaque", _safe_unparse(node))
    if isinstance(node, ast.BinOp):
        a = _eval(node.left, env, mod, depth + 1)
        b = _eval(node.right, env, mod, depth + 1)
        if isinstance(node.op, ast.Add):
            return _add(a, b)
        if isinstance(node.op, ast.Sub):
            return _sub(a, b)
        if isinstance(node.op, ast.Mult):
            return _mul(a, b)
        if isinstance(node.op, ast.FloorDiv):
            return _floordiv(a, b)
        if isinstance(node.op, ast.Mod):
            return _mod(a, b)
        if _contains(a, ("data",)) or _contains(b, ("data",)):
            return ("data",)
        return ("opaque", _safe_unparse(node))
    if isinstance(node, ast.Tuple):
        return ("tuple",) + tuple(
            _eval(el, env, mod, depth + 1) for el in node.elts)
    if isinstance(node, ast.Subscript):
        return _eval_subscript(node, env, mod, depth)
    if isinstance(node, ast.Call):
        return _eval_call(node, env, mod, depth)
    if isinstance(node, ast.Attribute) and node.attr == "itemsize":
        return _ITEMSIZE       # of the pool the dispatcher binds
    return ("opaque", _safe_unparse(node))


def _const_index(node: ast.expr) -> Optional[int]:
    if isinstance(node, ast.Constant) and isinstance(node.value, int):
        return node.value
    if (isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub)
            and isinstance(node.operand, ast.Constant)
            and isinstance(node.operand.value, int)):
        return -node.operand.value
    return None


def _eval_subscript(node: ast.Subscript, env: _Env, mod: _ModuleInfo,
                    depth: int) -> Expr:
    base = node.value
    # x.shape[i]
    if (isinstance(base, ast.Attribute) and base.attr == "shape"
            and isinstance(base.value, ast.Name)):
        i = _const_index(node.slice)
        if i is not None and i >= 0:
            return env.shape_axis(base.value.id, i)
        return ("opaque", _safe_unparse(node))
    if isinstance(base, ast.Name):
        if base.id in env.data_names:
            return ("data",)
        tup = env.vars.get(base.id)
        if tup is not None and tup[0] == "tuple":
            i = _const_index(node.slice)
            if i is not None and -len(tup[1:]) <= i < len(tup[1:]):
                return tup[1:][i]
    inner = _eval(base, env, mod, depth + 1)
    if _contains_any_data(inner):
        return ("data",)
    return ("opaque", _safe_unparse(node))


def _contains_any_data(e: Expr) -> bool:
    if e[0] == "tuple":
        return any(_contains_any_data(x) for x in e[1:])
    return _contains(e, ("data",))


def _eval_call(node: ast.Call, env: _Env, mod: _ModuleInfo,
               depth: int) -> Expr:
    # value.astype(dtype): shape/value-preserving for index math
    if (isinstance(node.func, ast.Attribute)
            and node.func.attr in ("astype", "copy")):
        return _eval(node.func.value, env, mod, depth + 1)
    name = dotted_name(node.func) or ""
    last = name.split(".")[-1]
    args = node.args
    if last in ("minimum", "min") and len(args) == 2:
        return _min(_eval(args[0], env, mod, depth + 1),
                    _eval(args[1], env, mod, depth + 1))
    if last in ("maximum", "max") and len(args) == 2:
        return _max(_eval(args[0], env, mod, depth + 1),
                    _eval(args[1], env, mod, depth + 1))
    if last == "div" and len(args) == 2:       # jax.lax.div on int32s
        return _floordiv(_eval(args[0], env, mod, depth + 1),
                         _eval(args[1], env, mod, depth + 1))
    if last == "rem" and len(args) == 2:
        return _mod(_eval(args[0], env, mod, depth + 1),
                    _eval(args[1], env, mod, depth + 1))
    if last in ("int32", "int64", "int8", "asarray") and len(args) == 1:
        return _eval(args[0], env, mod, depth + 1)
    if last == "program_id" and len(args) == 1:
        i = _const_index(args[0])
        return ("grid", i) if i is not None else ("opaque", "pid")
    if last == "num_programs" and len(args) == 1:
        i = _const_index(args[0])
        if i is not None and 0 <= i < len(env.grid_sizes):
            return env.grid_sizes[i]
        return ("opaque", "num_programs")
    # module-level helper with straight-line body + single return
    fn = mod.functions.get(name) if name else None
    if fn is not None and depth < _INLINE_DEPTH:
        return _inline(fn, node, env, mod, depth)
    out = ("opaque", _safe_unparse(node))
    if any(_contains_any_data(_eval(a, env, mod, depth + 1))
           for a in args):
        return ("data",)
    return out


def _inline(fn: ast.FunctionDef, call: ast.Call, env: _Env,
            mod: _ModuleInfo, depth: int) -> Expr:
    params = [a.arg for a in fn.args.args]
    child = _Env()
    child.grid_sizes = list(env.grid_sizes)
    child.local_fns = dict(env.local_fns)
    bound: Dict[str, Expr] = {}
    for p, a in zip(params, call.args):
        bound[p] = _eval(a, env, mod, depth + 1)
    for kw in call.keywords:
        if kw.arg:
            bound[kw.arg] = _eval(kw.value, env, mod, depth + 1)
    defaults = fn.args.defaults
    for p, d in zip(params[len(params) - len(defaults):], defaults):
        bound.setdefault(p, _eval(d, env, mod, depth + 1))
    child.vars.update(bound)
    ret: Optional[Expr] = None
    for stmt in fn.body:
        if isinstance(stmt, ast.Assign):
            _process_assign(stmt, child, mod)
        elif isinstance(stmt, ast.Return) and stmt.value is not None:
            ret = _eval(stmt.value, child, mod, depth + 1)
            break
        elif isinstance(stmt, (ast.Expr,)):   # docstring
            continue
        else:
            return ("opaque", _safe_unparse(call))
    return ret if ret is not None else ("opaque", _safe_unparse(call))


def _safe_unparse(node: ast.AST) -> str:
    try:
        return ast.unparse(node)[:80]
    except Exception:  # pragma: no cover - unparse is total on 3.9+
        return "<expr>"


def _process_assign(stmt: ast.stmt, env: _Env, mod: _ModuleInfo) -> None:
    if isinstance(stmt, ast.AnnAssign):
        if stmt.value is None or not isinstance(stmt.target, ast.Name):
            return
        targets: List[ast.expr] = [stmt.target]
        value: ast.expr = stmt.value
    elif isinstance(stmt, ast.Assign):
        if not stmt.targets:
            return
        targets = [stmt.targets[0]]
        value = stmt.value
    elif isinstance(stmt, ast.AugAssign):
        if not isinstance(stmt.target, ast.Name):
            return
        cur = env.vars.get(stmt.target.id, ("sym", stmt.target.id))
        v = _eval(stmt.value, env, mod)
        if isinstance(stmt.op, ast.Add):
            env.vars[stmt.target.id] = _add(cur, v)
        elif isinstance(stmt.op, ast.Sub):
            env.vars[stmt.target.id] = _sub(cur, v)
        elif isinstance(stmt.op, ast.Mult):
            env.vars[stmt.target.id] = _mul(cur, v)
        else:
            env.vars[stmt.target.id] = ("opaque", stmt.target.id)
        return
    else:
        return

    tgt = targets[0]
    # A, B, C = x.shape  -> dim syms + recorded axes
    if (isinstance(tgt, ast.Tuple)
            and isinstance(value, ast.Attribute) and value.attr == "shape"
            and isinstance(value.value, ast.Name)):
        arr = env.resolve_alias(value.value.id)
        axes = env.shapes.setdefault(arr, {})
        for k, el in enumerate(tgt.elts):
            if not isinstance(el, ast.Name):
                continue
            nm = el.id if el.id != "_" else f"{arr}.shape[{k}]"
            sym = ("dim", nm)
            if el.id != "_":
                env.vars[el.id] = sym
            axes.setdefault(k, sym)
        return
    # a, b = e1, e2  -> pairwise
    if (isinstance(tgt, ast.Tuple) and isinstance(value, ast.Tuple)
            and len(tgt.elts) == len(value.elts)):
        for el, v in zip(tgt.elts, value.elts):
            fake = ast.Assign(targets=[el], value=v)
            ast.copy_location(fake, stmt)
            _process_assign(fake, env, mod)
        return
    if not isinstance(tgt, ast.Name):
        return
    env.ast_vars[tgt.id] = value
    # t = x.shape[i]  -> dim sym + recorded axis
    if (isinstance(value, ast.Subscript)
            and isinstance(value.value, ast.Attribute)
            and value.value.attr == "shape"
            and isinstance(value.value.value, ast.Name)):
        i = _const_index(value.slice)
        if i is not None and i >= 0:
            arr = env.resolve_alias(value.value.value.id)
            sym = ("dim", tgt.id)
            env.vars[tgt.id] = sym
            env.shapes.setdefault(arr, {}).setdefault(i, sym)
            return
    if isinstance(value, ast.Name):
        env.aliases[tgt.id] = value.id
    elif (isinstance(value, ast.Call)
            and isinstance(value.func, ast.Attribute)
            and value.func.attr in ("astype", "copy")
            and isinstance(value.func.value, ast.Name)):
        env.aliases[tgt.id] = value.func.value.id
    env.vars[tgt.id] = _eval(value, env, mod)


# ------------------------------------------------------------ site parsing


@dataclass
class _Block:
    shape: Optional[Tuple[Expr, ...]]
    shape_nodes: Optional[List[ast.expr]]
    index_params: List[str]
    index_results: Optional[List[Expr]]
    index_text: str
    memory_space: str
    node: ast.expr


@dataclass
class _Site:
    call: ast.Call
    wrapper: ast.FunctionDef
    env: _Env
    grid: List[Expr]
    nsp: int
    in_specs: List[_Block]
    out_specs: List[_Block]
    out_dims: List[Optional[Tuple[Expr, ...]]]
    out_esizes: List[Optional[int]]
    scratch_nodes: List[ast.expr]
    kernel_fn: Optional[ast.FunctionDef]
    kernel_bound: Dict[str, Expr] = field(default_factory=dict)
    operands: List[Optional[str]] = field(default_factory=list)
    grid_param_names: List[str] = field(default_factory=list)
    vmem_expr: Optional[Expr] = None
    vmem_concrete: Optional[int] = None


def _is_pallas_call(node: ast.AST) -> bool:
    if not isinstance(node, ast.Call):
        return False
    name = dotted_name(node.func) or ""
    return name.split(".")[-1] == "pallas_call"


def _kw(call: ast.Call, name: str) -> Optional[ast.expr]:
    for kw in call.keywords:
        if kw.arg == name:
            return kw.value
    return None


def _resolve_node(node: Optional[ast.expr], env: _Env) -> \
        Optional[ast.expr]:
    """Follow a Name through the wrapper's raw assignments (spec lists and
    grid-spec objects are structural, not symbolic)."""
    seen = 0
    while isinstance(node, ast.Name) and seen < 8:
        nxt = env.ast_vars.get(node.id)
        if nxt is None:
            return node
        node = nxt
        seen += 1
    return node


def _spec_elements(node: Optional[ast.expr], env: _Env) -> List[ast.expr]:
    node = _resolve_node(node, env)
    if isinstance(node, (ast.List, ast.Tuple)):
        return list(node.elts)
    return [node] if node is not None else []


def _parse_block(node: Optional[ast.expr], env: _Env,
                 mod: _ModuleInfo, wrapper: ast.FunctionDef) -> _Block:
    node = _resolve_node(node, env)
    shape: Optional[Tuple[Expr, ...]] = None
    shape_nodes: Optional[List[ast.expr]] = None
    params: List[str] = []
    results: Optional[List[Expr]] = None
    text = ""
    space = ""
    if isinstance(node, ast.Call):
        shape_node = node.args[0] if node.args else _kw(node, "block_shape")
        index_node = (node.args[1] if len(node.args) > 1
                      else _kw(node, "index_map"))
        ms = _kw(node, "memory_space")
        if ms is not None:
            ms_name = dotted_name(ms) or ""
            if ms_name.split(".")[-1] in ("SMEM", "ANY"):
                space = ms_name.split(".")[-1]
        shape_node = _resolve_node(shape_node, env)
        if isinstance(shape_node, ast.Tuple):
            shape_nodes = list(shape_node.elts)
            shape = tuple(_eval(el, env, mod) for el in shape_nodes)
        index_node = _resolve_node(index_node, env)
        fn_def: Optional[ast.AST] = None
        if isinstance(index_node, ast.Lambda):
            fn_def = index_node
        elif isinstance(index_node, ast.Name):
            fn_def = env.local_fns.get(index_node.id) \
                or mod.functions.get(index_node.id)
        if fn_def is not None:
            params, results, text = _eval_index_fn(fn_def, env, mod)
    return _Block(shape, shape_nodes, params, results, text, space,
                  node if node is not None else wrapper)


def _eval_index_fn(fn: ast.AST, env: _Env, mod: _ModuleInfo) -> \
        Tuple[List[str], Optional[List[Expr]], str]:
    n_grid = len(env.grid_sizes)
    child = env.child()
    if isinstance(fn, ast.Lambda):
        arg_names = [a.arg for a in fn.args.args]
        body: Any = fn.body
        stmts: List[ast.stmt] = []
        vararg = fn.args.vararg
    else:
        assert isinstance(fn, ast.FunctionDef)
        arg_names = [a.arg for a in fn.args.args]
        stmts = fn.body
        body = None
        vararg = fn.args.vararg
    for i, nm in enumerate(arg_names):
        if i < n_grid:
            child.grid_params[nm] = i
        else:
            child.data_names.add(nm)
    if vararg is not None:
        child.data_names.add(vararg.arg)
    if stmts:
        for stmt in stmts:
            if isinstance(stmt, ast.Return):
                body = stmt.value
                break
            _process_assign(stmt, child, mod)
    if body is None:
        return arg_names, None, ""
    text = _safe_unparse(body)
    out = _eval(body, child, mod)
    if out[0] == "tuple":
        return arg_names, list(out[1:]), text
    return arg_names, [out], text


def _collect_sites(src: SourceFile, mod: _ModuleInfo) -> List[_Site]:
    sites: List[_Site] = []
    for call in ast.walk(src.tree):
        if not _is_pallas_call(call):
            continue
        wrapper = src.enclosing_scope(call.lineno)
        if not isinstance(wrapper, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        site = _parse_site(call, wrapper, src, mod)
        if site is not None:
            sites.append(site)
    return sites


def _parse_site(call: ast.Call, wrapper: ast.FunctionDef, src: SourceFile,
                mod: _ModuleInfo) -> Optional[_Site]:
    env = _Env()
    for a in wrapper.args.args + wrapper.args.kwonlyargs:
        env.vars[a.arg] = ("sym", a.arg)

    def scan(stmts: List[ast.stmt]) -> None:
        for stmt in stmts:
            if isinstance(stmt, ast.FunctionDef):
                env.local_fns[stmt.name] = stmt
                continue
            if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                if stmt.lineno < call.lineno:
                    _process_assign(stmt, env, mod)
            elif isinstance(stmt, (ast.If, ast.For, ast.While, ast.With,
                                   ast.Try)):
                for fld in ("body", "orelse", "finalbody"):
                    scan(getattr(stmt, fld, []) or [])
                for h in getattr(stmt, "handlers", []) or []:
                    scan(h.body)

    scan(wrapper.body)

    grid_node = _kw(call, "grid")
    in_specs_node = _kw(call, "in_specs")
    out_specs_node = _kw(call, "out_specs")
    scratch_node = _kw(call, "scratch_shapes")
    nsp = 0
    gs_node = _resolve_node(_kw(call, "grid_spec"), env)
    if isinstance(gs_node, ast.Call):
        nsp_node = _kw(gs_node, "num_scalar_prefetch")
        nsp = _const_index(nsp_node) or 0 if nsp_node is not None else 0
        grid_node = _kw(gs_node, "grid") or grid_node
        in_specs_node = _kw(gs_node, "in_specs") or in_specs_node
        out_specs_node = _kw(gs_node, "out_specs") or out_specs_node
        scratch_node = _kw(gs_node, "scratch_shapes") or scratch_node

    grid_node = _resolve_node(grid_node, env)
    grid: List[Expr] = []
    if isinstance(grid_node, ast.Tuple):
        grid = [_eval(el, env, mod) for el in grid_node.elts]
    elif grid_node is not None:
        g = _eval(grid_node, env, mod)
        grid = list(g[1:]) if g[0] == "tuple" else [g]
    if not grid:
        return None
    env.grid_sizes = grid

    in_specs = [_parse_block(n, env, mod, wrapper)
                for n in _spec_elements(in_specs_node, env)]
    out_specs = [_parse_block(n, env, mod, wrapper)
                 for n in _spec_elements(out_specs_node, env)]

    out_dims: List[Optional[Tuple[Expr, ...]]] = []
    out_esizes: List[Optional[int]] = []
    for osn in _spec_elements(_kw(call, "out_shape"), env):
        osn = _resolve_node(osn, env)
        dims: Optional[Tuple[Expr, ...]] = None
        esize: Optional[int] = None
        if isinstance(osn, ast.Call):
            shp = osn.args[0] if osn.args else _kw(osn, "shape")
            shp = _resolve_node(shp, env)
            if isinstance(shp, ast.Tuple):
                dims = tuple(_eval(el, env, mod) for el in shp.elts)
            dt = osn.args[1] if len(osn.args) > 1 else _kw(osn, "dtype")
            esize = _esize_of(dt)
        out_dims.append(dims)
        out_esizes.append(esize)

    scratch_nodes = _spec_elements(scratch_node, env)

    kernel_fn: Optional[ast.FunctionDef] = None
    bound: Dict[str, Expr] = {}
    if call.args:
        kn = call.args[0]
        if isinstance(kn, ast.Call) and \
                (dotted_name(kn.func) or "").split(".")[-1] == "partial":
            if kn.args and isinstance(kn.args[0], ast.Name):
                kernel_fn = env.local_fns.get(kn.args[0].id) \
                    or mod.functions.get(kn.args[0].id)
            for kw in kn.keywords:
                if kw.arg:
                    bound[kw.arg] = _eval(kw.value, env, mod)
        elif isinstance(kn, ast.Name):
            kernel_fn = env.local_fns.get(kn.id) or mod.functions.get(kn.id)

    operands: List[Optional[str]] = []
    parent = src._parents.get(call)
    if isinstance(parent, ast.Call) and parent.func is call:
        for arg in parent.args:
            arg_r = arg
            operands.append(arg_r.id if isinstance(arg_r, ast.Name)
                            else None)
    # positional layout: [nsp prefetch refs][inputs][outputs][scratch]
    operands = operands[nsp:] if len(operands) > nsp else []

    grid_names: List[str] = []
    for spec in out_specs + in_specs:
        if spec.index_params:
            grid_names = spec.index_params[:len(grid)]
            break

    return _Site(call=call, wrapper=wrapper, env=env, grid=grid, nsp=nsp,
                 in_specs=in_specs, out_specs=out_specs,
                 out_dims=out_dims, out_esizes=out_esizes,
                 scratch_nodes=scratch_nodes, kernel_fn=kernel_fn,
                 kernel_bound=bound, operands=operands,
                 grid_param_names=grid_names)


def _esize_of(node: Optional[ast.expr]) -> Optional[int]:
    if node is None:
        return None
    name = dotted_name(node) or ""
    return _DTYPE_BYTES.get(name.split(".")[-1])


# ------------------------------------------------------------------ checks


def _axis_ok(dim: Expr, idx: Expr, blk: Expr,
             grid: Sequence[Expr]) -> Optional[str]:
    """None = proven-safe or undecidable (quiet); else a violation tag."""
    if _contains(idx, ("data",)) or _contains(blk, ("data",)) \
            or _contains(dim, ("data",)):
        return None   # runtime bounds wrapper owns data-dependent axes
    end_excess = _sub(dim, _add(_mul(idx, blk), blk))
    if not _prove_nonneg(end_excess, grid):
        # definite over-run: exists a grid coord with end > dim
        overrun = _sub(_add(_mul(idx, blk), blk), _add(dim, _c(1)))
        if _prove_nonneg(overrun, grid, maximize_grid=True):
            return "overrun"
    if not _prove_nonneg(idx, grid):
        under = _sub(_neg(idx), _c(1))
        if _prove_nonneg(under, grid, maximize_grid=True):
            return "negative"
    return None


def _check_bounds(src: SourceFile, site: _Site) -> List[Finding]:
    out: List[Finding] = []
    wrapper = site.wrapper.name
    specs: List[Tuple[str, _Block, Optional[Tuple[Expr, ...]]]] = []
    for i, spec in enumerate(site.in_specs):
        dims: Optional[Tuple[Expr, ...]] = None
        if i < len(site.operands) and site.operands[i] and spec.shape:
            nm = site.operands[i]
            dims = tuple(site.env.shape_axis(nm, ax)
                         for ax in range(len(spec.shape)))
        specs.append((f"in_specs[{i}]", spec, dims))
    for i, spec in enumerate(site.out_specs):
        dims = site.out_dims[i] if i < len(site.out_dims) else None
        specs.append((f"out_specs[{i}]", spec, dims))
    for label, spec, dims in specs:
        if spec.shape is None or spec.index_results is None:
            continue
        if dims is None or len(dims) != len(spec.shape):
            continue
        if len(spec.index_results) != len(spec.shape):
            continue
        for ax in range(len(spec.shape)):
            tag = _axis_ok(dims[ax], spec.index_results[ax],
                           spec.shape[ax], site.grid)
            if tag is None:
                continue
            what = ("block end index_map*block_shape + block_shape "
                    "exceeds the operand extent"
                    if tag == "overrun"
                    else "block index goes negative")
            out.append(make_finding(
                src, "SWL901", spec.node,
                f"out-of-bounds block in {wrapper} {label} axis {ax}: "
                f"{what} on some grid coordinate (index map "
                f"'{spec.index_text}', block dim "
                f"{_pretty(spec.shape[ax])}, operand dim "
                f"{_pretty(dims[ax])}, grid "
                f"{'x'.join(_pretty(g) for g in site.grid)})"))
    return out


def _revisit_dims(src: SourceFile, site: _Site) -> Set[str]:
    dims: Set[str] = set()
    revs = src.directives.revisits
    lo = min([site.wrapper.lineno]
             + [d.lineno for d in site.wrapper.decorator_list]) - 1
    hi = site.wrapper.end_lineno or site.wrapper.lineno
    for line, names in revs.items():
        if lo <= line <= hi:
            dims.update(names)
    return dims


def _check_write_race(src: SourceFile, site: _Site) -> List[Finding]:
    out: List[Finding] = []
    if len(site.grid) < 2:
        return out
    sanctioned = _revisit_dims(src, site)
    for oi, spec in enumerate(site.out_specs):
        if spec.index_results is None:
            continue
        used: Set[int] = set()
        for res in spec.index_results:
            stack = [res]
            while stack:
                e = stack.pop()
                if e[0] == "grid":
                    used.add(e[1])
                elif e[0] in _COMPOSITE:
                    stack.extend([e[1], e[2]])
        for g in range(len(site.grid) - 1):   # innermost axis is the
            if g in used:                     # sequential-accum idiom
                continue
            name = (site.grid_param_names[g]
                    if g < len(site.grid_param_names) else str(g))
            if str(g) in sanctioned or name in sanctioned:
                continue
            out.append(make_finding(
                src, "SWL902", spec.node,
                f"grid write race in {site.wrapper.name} out_specs[{oi}]: "
                f"index map '{spec.index_text}' ignores grid axis {g} "
                f"('{name}') — every value of that coordinate writes the "
                f"same output block; declare `# swarmlint: "
                f"revisit[{name}]` if the revisit is an accumulate/"
                f"finalize by design"))
    return out


def _block_bytes(spec: _Block, esize: Optional[int]) -> \
        Tuple[Optional[Expr], Optional[int]]:
    """(symbolic bytes, concrete bytes or None) for one block."""
    if spec.shape is None:
        return None, None
    e = esize or 4
    total: Expr = _c(e)
    conc: Optional[int] = e
    for d in spec.shape:
        total = _mul(total, d)
        if conc is not None and d[0] == "const":
            conc *= d[1]
        else:
            conc = None
    return total, conc


def _scratch_bytes(node: ast.expr, env: _Env, mod: _ModuleInfo) -> \
        Tuple[Optional[Expr], Optional[int], bool]:
    """(symbolic bytes, concrete bytes, is_vmem) for one scratch shape."""
    node = _resolve_node(node, env)
    if not isinstance(node, ast.Call):
        return None, None, False
    name = (dotted_name(node.func) or "").split(".")[-1]
    if name not in ("VMEM", "SMEM"):
        return None, None, False
    if name == "SMEM":
        return None, None, False
    shp = _resolve_node(node.args[0] if node.args else None, env)
    dt = node.args[1] if len(node.args) > 1 else None
    esize = _esize_of(dt) or 4
    if not isinstance(shp, ast.Tuple):
        return None, None, True
    total: Expr = _c(esize)
    conc: Optional[int] = esize
    if isinstance(dt, ast.Attribute) and dt.attr == "dtype":
        total, conc = _ITEMSIZE, None    # a buffer of the operand's dtype
    for el in shp.elts:
        d = _eval(el, env, mod)
        total = _mul(total, d)
        if conc is not None and d[0] == "const":
            conc *= d[1]
        else:
            conc = None
    return total, conc, True


def _check_vmem(src: SourceFile, site: _Site,
                mod: _ModuleInfo) -> List[Finding]:
    total_expr: Expr = _c(0)
    total_conc: Optional[int] = 0
    all_known = True
    pairs: List[Tuple[_Block, Optional[int]]] = []
    for spec in site.in_specs:
        pairs.append((spec, None))
    for i, spec in enumerate(site.out_specs):
        pairs.append((spec,
                      site.out_esizes[i] if i < len(site.out_esizes)
                      else None))
    for spec, esize in pairs:
        if spec.memory_space in ("SMEM", "ANY"):
            # SMEM is not VMEM; an ANY operand stays where it is (HBM)
            # and the kernel copies what it needs into its own scratch
            continue
        sym, conc = _block_bytes(spec, esize)
        if sym is None:
            all_known = False
            continue
        # Pallas double-buffers pipelined operand blocks
        total_expr = _add(total_expr, _mul(_c(2), sym))
        if conc is not None and total_conc is not None:
            total_conc += 2 * conc
        else:
            total_conc = None
    for snode in site.scratch_nodes:
        sym, conc, is_vmem = _scratch_bytes(snode, site.env, mod)
        if not is_vmem:
            continue
        if sym is None:
            all_known = False
            continue
        total_expr = _add(total_expr, sym)
        if conc is not None and total_conc is not None:
            total_conc += conc
        else:
            total_conc = None
    site.vmem_expr = total_expr if all_known else None
    site.vmem_concrete = total_conc if all_known else None
    if total_conc is None or not all_known or total_conc == 0:
        return []
    budget = vmem_budget()
    mib = total_conc / 2 ** 20
    bmib = budget / 2 ** 20
    if total_conc > budget:
        return [make_finding(
            src, "SWL903", site.call,
            f"VMEM budget overflow in {site.wrapper.name}: per-grid-step "
            f"footprint {mib:.1f} MiB (double-buffered blocks + scratch) "
            f"exceeds the {bmib:.0f} MiB platform budget — the kernel "
            f"will fail to lower or spill")]
    if total_conc >= 0.8 * budget:
        return [make_finding(
            src, "SWL903", site.call,
            f"VMEM budget pressure in {site.wrapper.name}: per-grid-step "
            f"footprint {mib:.1f} MiB is over 80% of the {bmib:.0f} MiB "
            f"platform budget — one more operand or a dtype widening "
            f"tips it over")]
    return []


def _check_tiling(src: SourceFile, site: _Site) -> List[Finding]:
    out: List[Finding] = []
    pairs: List[Tuple[str, _Block, Optional[int]]] = []
    for i, spec in enumerate(site.in_specs):
        pairs.append((f"in_specs[{i}]", spec, None))
    for i, spec in enumerate(site.out_specs):
        pairs.append((f"out_specs[{i}]", spec,
                      site.out_esizes[i] if i < len(site.out_esizes)
                      else None))
    for label, spec, esize in pairs:
        if spec.memory_space == "SMEM" or spec.shape is None:
            continue
        if len(spec.shape) < 2:
            continue
        sub, lane = spec.shape[-2], spec.shape[-1]
        need_sub = _SUBLANE.get(esize or 4, 8)
        if lane[0] == "const" and lane[1] % _LANE != 0:
            out.append(make_finding(
                src, "SWL904", spec.node,
                f"tiling misalignment in {site.wrapper.name} {label}: "
                f"lane dim {lane[1]} is not a multiple of {_LANE} — the "
                f"block occupies full {need_sub}x{_LANE} tiles anyway "
                f"and the remainder lanes are dead issue slots"))
        # a 1-row sublane group is the idiomatic per-row block (decode q,
        # single-page KV): degenerate, not misaligned — skip it
        if sub[0] == "const" and sub[1] > 1 and sub[1] % need_sub != 0:
            dt = {8: "f32", 16: "bf16", 32: "int8"}.get(need_sub, "f32")
            out.append(make_finding(
                src, "SWL904", spec.node,
                f"tiling misalignment in {site.wrapper.name} {label}: "
                f"sublane dim {sub[1]} is not a multiple of {need_sub} "
                f"(the {dt} tile is {need_sub}x{_LANE}) — pad or retile "
                f"the block"))
    return out


# --------------------------------------------------- SWL905: store coverage


def _kernel_env(site: _Site, mod: _ModuleInfo) -> \
        Tuple[_Env, List[str]]:
    """Env for the kernel body + the output ref parameter names."""
    env = _Env()
    env.grid_sizes = list(site.grid)
    fn = site.kernel_fn
    assert fn is not None
    params = [a.arg for a in fn.args.args]
    n_in = len(site.in_specs)
    n_out = max(len(site.out_specs), 1)
    pos = 0
    for i in range(site.nsp):
        if pos < len(params):
            env.data_names.add(params[pos])
            pos += 1
    in_names = params[pos:pos + n_in]
    for i, nm in enumerate(in_names):
        if site.in_specs[i].shape is not None:
            env.shapes[nm] = dict(enumerate(site.in_specs[i].shape))
    pos += n_in
    out_names = params[pos:pos + n_out]
    for i, nm in enumerate(out_names):
        if i < len(site.out_specs) and site.out_specs[i].shape is not None:
            env.shapes[nm] = dict(enumerate(site.out_specs[i].shape))
    for kwo in fn.args.kwonlyargs:
        if kwo.arg in site.kernel_bound:
            env.vars[kwo.arg] = site.kernel_bound[kwo.arg]
    for nm, v in site.kernel_bound.items():
        env.vars.setdefault(nm, v)
    return env, out_names


def _when_cond(stmt: ast.FunctionDef) -> Optional[ast.expr]:
    for dec in stmt.decorator_list:
        if isinstance(dec, ast.Call):
            name = (dotted_name(dec.func) or "").split(".")[-1]
            if name == "when" and dec.args:
                return dec.args[0]
    return None


def _split_conj(node: ast.expr) -> List[ast.expr]:
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.BitAnd):
        return _split_conj(node.left) + _split_conj(node.right)
    return [node]


def _guard_status(cond: ast.expr, env: _Env, mod: _ModuleInfo,
                  grid: Sequence[Expr]) -> str:
    """'ok' (satisfiable / unknown), 'unsat' (provably never true over
    the grid), or 'data' (scalar-prefetch dependent)."""
    if not isinstance(cond, ast.Compare) or len(cond.ops) != 1:
        e = _eval(cond, env, mod)
        return "data" if _contains(e, ("data",)) else "ok"
    lhs = _eval(cond.left, env, mod)
    rhs = _eval(cond.comparators[0], env, mod)
    if _contains(lhs, ("data",)) or _contains(rhs, ("data",)):
        return "data"
    if not isinstance(cond.ops[0], ast.Eq):
        return "ok"
    const, terms = _affine(_sub(lhs, rhs))
    grid_atoms = [(a, co) for a, co in terms.items() if a[0] == "grid"]
    if len(grid_atoms) != 1 or abs(grid_atoms[0][1]) != 1:
        if not terms and const != 0:
            return "unsat"    # constant != constant
        return "ok"
    atom, co = grid_atoms[0]
    rest = _rebuild(const, {a: c for a, c in terms.items() if a != atom})
    v = _neg(rest) if co == 1 else rest     # the value g must take
    i = atom[1]
    if i >= len(grid):
        return "ok"
    # unsat iff v < 0 for ALL grid coords, or v >= grid[i] for all
    if _prove_nonneg(_sub(_neg(v), _c(1)), grid):
        return "unsat"
    if _prove_nonneg(_sub(v, grid[i]), grid):
        return "unsat"
    return "ok"


def _check_coverage(src: SourceFile, site: _Site,
                    mod: _ModuleInfo) -> List[Finding]:
    if site.kernel_fn is None:
        return []
    env, out_names = _kernel_env(site, mod)
    if not out_names:
        return []
    # walk the kernel body in order, tracking @pl.when guard nesting and
    # symbolic assignments; collect (ref name, guard stack) per store
    stores: Dict[str, List[List[ast.expr]]] = {nm: [] for nm in out_names}

    def dma_stores(stmt: ast.stmt, guards: List[ast.expr]) -> None:
        # an output left in HBM (``ANY``) is written by the kernel's own
        # copies: ``make_async_copy(src, out.at[...], sem)`` is its store
        for call in ast.walk(stmt):
            if (isinstance(call, ast.Call) and len(call.args) >= 2
                    and getattr(call.func, "attr", None)
                    == "make_async_copy"):
                dst = call.args[1]
                while isinstance(dst, (ast.Subscript, ast.Attribute,
                                       ast.Call)):
                    dst = dst.func if isinstance(dst, ast.Call) \
                        else dst.value
                if isinstance(dst, ast.Name) and dst.id in stores:
                    stores[dst.id].append(list(guards))

    def walk(stmts: List[ast.stmt], guards: List[ast.expr]) -> None:
        for stmt in stmts:
            if isinstance(stmt, ast.FunctionDef):
                cond = _when_cond(stmt)
                inner = guards + ([cond] if cond is not None else [])
                walk(stmt.body, inner)
                continue
            if isinstance(stmt, (ast.Return, ast.Expr, ast.Assign)):
                dma_stores(stmt, guards)
            if isinstance(stmt, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                tgt = (stmt.targets[0] if isinstance(stmt, ast.Assign)
                       else stmt.target)
                if (isinstance(tgt, ast.Subscript)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id in stores):
                    stores[tgt.value.id].append(list(guards))
                else:
                    _process_assign(stmt, env, mod)
                continue
            for fld in ("body", "orelse", "finalbody"):
                sub = getattr(stmt, fld, None)
                if sub:
                    walk(sub, guards)

    walk(site.kernel_fn.body, [])
    out: List[Finding] = []
    for nm in out_names:
        if not stores[nm]:
            out.append(make_finding(
                src, "SWL905", site.kernel_fn,
                f"unwritten output in kernel {site.kernel_fn.name} "
                f"(called from {site.wrapper.name}): no store to output "
                f"ref '{nm}' anywhere in the kernel body — every grid "
                f"cell leaves the output block as stale VMEM garbage"))
            continue
        witnessed = False
        all_unsat = True
        for guards in stores[nm]:
            statuses = [ _guard_status(c, env, mod, site.grid)
                         for g in guards for c in _split_conj(g) ]
            if any(s == "unsat" for s in statuses):
                continue
            all_unsat = False
            if all(s == "ok" for s in statuses):
                witnessed = True
                break
            # 'data' guards: static analysis cannot decide coverage;
            # the runtime canary owns it — counts as coverage here
            witnessed = True
            break
        if not witnessed and all_unsat:
            out.append(make_finding(
                src, "SWL905", site.kernel_fn,
                f"unwritten output in kernel {site.kernel_fn.name} "
                f"(called from {site.wrapper.name}): every store to "
                f"output ref '{nm}' sits under a @pl.when guard that is "
                f"provably unsatisfiable over the grid "
                f"{'x'.join(_pretty(g) for g in site.grid)}"))
    return out


# ----------------------------------------------- in-kernel pl.ds slices


def _check_kernel_slices(src: SourceFile, site: _Site,
                         mod: _ModuleInfo) -> List[Finding]:
    if site.kernel_fn is None:
        return []
    env, _ = _kernel_env(site, mod)
    out: List[Finding] = []

    def scan(stmts: List[ast.stmt]) -> None:
        for stmt in stmts:
            if isinstance(stmt,
                          (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                _process_assign(stmt, env, mod)
            for node in ast.walk(stmt):
                if isinstance(node, ast.Subscript):
                    _check_sub(node)
            if isinstance(stmt, ast.FunctionDef):
                scan(stmt.body)

    def _check_sub(node: ast.Subscript) -> None:
        if not isinstance(node.value, ast.Name):
            return
        ref = node.value.id
        axes = env.shapes.get(ref)
        if not axes:
            return
        elts = (list(node.slice.elts)
                if isinstance(node.slice, ast.Tuple) else [node.slice])
        for ax, el in enumerate(elts):
            if not (isinstance(el, ast.Call)
                    and (dotted_name(el.func) or "").split(".")[-1]
                    == "ds"):
                continue
            if ax not in axes or len(el.args) < 2:
                continue
            start = _eval(el.args[0], env, mod)
            size = _eval(el.args[1], env, mod)
            if _contains(start, ("data",)) or _contains(size, ("data",)):
                continue
            tag = None
            end_excess = _sub(axes[ax], _add(start, size))
            if not _prove_nonneg(end_excess, site.grid):
                overrun = _sub(_add(start, size),
                               _add(axes[ax], _c(1)))
                if _prove_nonneg(overrun, site.grid,
                                 maximize_grid=True):
                    tag = "overrun"
            if tag == "overrun" or (
                    not _prove_nonneg(start, site.grid)
                    and _prove_nonneg(_sub(_neg(start), _c(1)),
                                      site.grid, maximize_grid=True)):
                out.append(make_finding(
                    src, "SWL901", el,
                    f"out-of-bounds pl.ds slice in kernel "
                    f"{site.kernel_fn.name}: ref '{ref}' axis {ax} "
                    f"slice [{_pretty(start)}:+{_pretty(size)}] can "
                    f"leave [0, {_pretty(axes[ax])})"))

    scan(site.kernel_fn.body)
    return out


# -------------------------------------------------------------- entrypoint


def check(src: SourceFile) -> List[Finding]:
    if "pallas_call" not in src.text:
        return []
    mod = _ModuleInfo(src)
    findings: List[Finding] = []
    for site in _collect_sites(src, mod):
        findings.extend(_check_bounds(src, site))
        findings.extend(_check_write_race(src, site))
        findings.extend(_check_vmem(src, site, mod))
        findings.extend(_check_tiling(src, site))
        findings.extend(_check_coverage(src, site, mod))
        findings.extend(_check_kernel_slices(src, site, mod))
    return findings


# ------------------------------------------------- swarmprof estimate API


def _default_kernel_paths() -> List[str]:
    here = os.path.dirname(os.path.abspath(__file__))
    ops = os.path.join(os.path.dirname(here), "ops")
    return [os.path.join(ops, n) for n in sorted(os.listdir(ops))
            if n.endswith(".py")] if os.path.isdir(ops) else []


_SITE_CACHE: Dict[str, Tuple[Tuple[int, int], List[Dict[str, Any]]]] = {}


def static_vmem_table(paths: Optional[Sequence[str]] = None) -> \
        List[Dict[str, Any]]:
    """Per-pallas_call static VMEM footprints over ``paths`` (default:
    the in-package ops/ dir). Each row: kernel, wrapper, path, line,
    formula (pretty symbolic bytes), concrete_bytes (int | None), and
    the raw expression under ``expr`` for :func:`eval_with_dims`."""
    from .core import _parse_source

    rows: List[Dict[str, Any]] = []
    for path in (list(paths) if paths else _default_kernel_paths()):
        try:
            st = os.stat(path)
            stamp = (st.st_mtime_ns, st.st_size)
            hit = _SITE_CACHE.get(path)
            if hit is not None and hit[0] == stamp:
                rows.extend(hit[1])
                continue
            src = _parse_source(path)
        except (OSError, SyntaxError):
            continue
        if "pallas_call" not in src.text:
            _SITE_CACHE[path] = (stamp, [])
            continue
        mod = _ModuleInfo(src)
        file_rows: List[Dict[str, Any]] = []
        for site in _collect_sites(src, mod):
            _check_vmem(src, site, mod)   # populates vmem_expr/_concrete
            if site.vmem_expr is None:
                continue
            file_rows.append({
                "kernel": (site.kernel_fn.name if site.kernel_fn
                           else "<lambda>"),
                "wrapper": site.wrapper.name,
                "path": os.path.normpath(src.path).replace(os.sep, "/"),
                "line": site.call.lineno,
                "formula": _pretty(site.vmem_expr),
                "concrete_bytes": site.vmem_concrete,
                "expr": site.vmem_expr,
            })
        _SITE_CACHE[path] = (stamp, file_rows)
        rows.extend(file_rows)
    return rows


def estimate_vmem(kernel: str, dims: Dict[str, int],
                  paths: Optional[Sequence[str]] = None) -> Optional[int]:
    """Static VMEM footprint (bytes) of the first pallas_call site whose
    kernel or wrapper name contains ``kernel``, evaluated under concrete
    ``dims`` (trace-time shapes). None when no site matches or a dim is
    unbound — callers treat that as 'no estimate', never an error."""
    rows = static_vmem_table(paths)
    # the kernel of that very name before one whose name contains it
    # (`_ragged_prefill_kernel` is a prefix of its `_quant` twin's)
    named = [r for r in rows if kernel in (r["kernel"], r["wrapper"])]
    for row in named or [r for r in rows if kernel in r["kernel"]
                         or kernel in r["wrapper"]]:
        got = eval_with_dims(row["expr"], dims)
        if got is not None:
            return got
    return None
