"""recompile-hazard checks (SWL201/SWL202/SWL203/SWL204).

Every compiled variant costs a quarter of a minute to a minute and a half
at 8B widths (backend/engine.py warmup docstring), so a silent recompile
mid-traffic is
a latency cliff, not a nuisance. Four statically checkable shapes:

- SWL201: ``jax.jit`` (or ``pmap``) *called* inside a loop or a hot
  function. ``jit`` caches by wrapper identity — a fresh wrapper per call
  is a compile-cache miss per call.
- SWL202: call sites of known jit-wrapped callables whose argument
  signature can vary per call: a non-constant value in a declared
  ``static_argnums`` position (one compile per distinct value), an
  f-string argument (distinct string per call — and strings are static by
  hashability), a ``len(...)`` scalar (weak-type/dtype churn re-traces),
  or a dict display in a static position (ordering-dependent hash).
- SWL203: the static twin of ``tests/test_rolling_drift.py``'s precompile
  drift guard — in any class that defines ``warmup``/``warmup_call_plan``,
  every attribute assigned from ``jax.jit(...)`` must be *reachable* from
  those methods (directly, through attribute aliases like
  ``_decode_variants``, or through helper methods such as the mirrored-
  call table). An unreachable jit entry point means the first real request
  through it pays a cold compile while every in-flight request waits.
- SWL204: a host array whose SHAPE derives from a runtime ``len(...)``
  / row count (``np.zeros((len(pending), K))`` and friends) handed to a
  jit-wrapped callable — directly or through a one-hop local binding.
  Every distinct count is a distinct traced shape, i.e. a fresh compile:
  the "compile mine" class PROFILE r4 stepped on twice (the eager
  page-table zeroing and the first ``_extract_lane`` dispatch). The fix
  is always the same — pad to a fixed wave size or bucket the count.
- SWL205: the SCALAR-laundered twin of SWL204, scoped to ``# swarmlint:
  hot`` kernel-dispatch code — ``n = len(rows)`` / ``n = arr.shape[0]``
  descriptor math whose name then shapes an array constructor handed to
  a jit-wrapped callable. The ragged packed-wave path's
  variant-explosion hazard (ISSUE 11): a wave width copied straight off
  the descriptors compiles one program per distinct token count, where
  the engine's width ladder (``_ragged_width_for`` / ``_rows_for``)
  quantizes it to a warmed bucket. Routing the count through such a
  bucketing helper is exactly what breaks the taint — by design.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .core import Finding, SourceFile, dotted_name, make_finding

JIT_NAMES = ("jit", "pmap")
WARMUP_METHODS = ("warmup", "warmup_call_plan", "precompile")


def _is_jit_call(node: ast.Call) -> bool:
    # func must be a plain name/attribute: `jax.jit(f)(...)` is an
    # *invocation* of an anonymous wrapper, not a reusable entry point
    if not isinstance(node.func, (ast.Name, ast.Attribute)):
        return False
    name = dotted_name(node.func)
    return bool(name) and name.split(".")[-1] in JIT_NAMES


def _static_positions(node: ast.Call) -> Tuple[Set[int], bool]:
    """(declared static_argnums positions, has_any_static_decl)."""
    positions: Set[int] = set()
    has_static = False
    for kw in node.keywords:
        if kw.arg == "static_argnums":
            has_static = True
            v = kw.value
            elts = v.elts if isinstance(v, (ast.Tuple, ast.List)) else [v]
            for e in elts:
                if isinstance(e, ast.Constant) and isinstance(e.value, int):
                    positions.add(e.value)
        elif kw.arg == "static_argnames":
            has_static = True
    return positions, has_static


def _ref_names(node: ast.AST,
               class_names: Optional[Set[str]] = None) -> Set[str]:
    """Names referenced under ``node`` that live in the class namespace:
    ``self.<attr>`` accesses always; bare names only when they match a
    method or class-level binding (``class_names``) — method locals must
    not leak into the reachability closure (a local named like a method
    would bridge unrelated call graphs)."""
    out: Set[str] = set()
    for n in ast.walk(node):
        if isinstance(n, ast.Name):
            if class_names is None or n.id in class_names:
                out.add(n.id)
        elif isinstance(n, ast.Attribute):
            if isinstance(n.value, ast.Name) and n.value.id == "self":
                out.add(n.attr)
    return out


def check(src: SourceFile) -> List[Finding]:
    findings: List[Finding] = []
    findings.extend(_check_jit_sites(src))
    findings.extend(_check_call_sites(src))
    findings.extend(_check_warmup_coverage(src))
    findings.extend(_check_len_shaped_args(src))
    findings.extend(_check_descriptor_shape_math(src))
    return findings


# ----------------------------------------------------------- SWL201 + decl

def _check_jit_sites(src: SourceFile) -> List[Finding]:
    findings: List[Finding] = []

    def visit(node: ast.AST, in_loop: bool, hot_fn: Optional[str]) -> None:
        for child in ast.iter_child_nodes(node):
            child_loop = in_loop or isinstance(child, (ast.For, ast.While))
            child_hot = hot_fn
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # a nested def resets loop context (the loop runs the
                # def statement, not necessarily the body) but inherits
                # hotness; a def directly inside a loop IS re-created per
                # iteration, so jits inside it still churn — keep in_loop.
                child_hot = (child.name if (hot_fn or src.is_hot(child))
                             else None)
            if isinstance(child, ast.Call) and _is_jit_call(child):
                if child_loop:
                    findings.append(make_finding(
                        src, "SWL201", child,
                        "`jax.jit` called inside a loop — builds a fresh "
                        "wrapper (and compiles) every iteration; hoist the "
                        "jit to module/init scope"))
                elif child_hot:
                    findings.append(make_finding(
                        src, "SWL201", child,
                        f"`jax.jit` called inside hot function "
                        f"`{child_hot}` — a fresh wrapper per call never "
                        f"hits the compile cache"))
            visit(child, child_loop, child_hot)

    visit(src.tree, False, None)
    return findings


# ------------------------------------------------------------------ SWL202

def _collect_jitted(src: SourceFile) -> Dict[str, Tuple[Set[int], bool]]:
    """last-segment callable name -> (static positions, has_static)."""
    out: Dict[str, Tuple[Set[int], bool]] = {}
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call) \
                and _is_jit_call(node.value):
            static, has_static = _static_positions(node.value)
            for tgt in node.targets:
                tname = dotted_name(tgt)
                if tname:
                    out[tname.split(".")[-1]] = (static, has_static)
    return out


def _is_constantish(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant):
        return True
    if isinstance(node, ast.UnaryOp):
        return _is_constantish(node.operand)
    if isinstance(node, (ast.Tuple, ast.List)):
        return all(_is_constantish(e) for e in node.elts)
    # self.X / module.CONST: plausibly fixed config — give the benefit of
    # the doubt (the baseline absorbs deliberate per-deployment statics)
    if isinstance(node, ast.Attribute):
        return True
    return False


def _check_call_sites(src: SourceFile) -> List[Finding]:
    findings: List[Finding] = []
    jitted = _collect_jitted(src)
    if not jitted:
        return findings
    for node in ast.walk(src.tree):
        if not isinstance(node, ast.Call):
            continue
        name = dotted_name(node.func)
        if name is None:
            continue
        last = name.split(".")[-1]
        if last not in jitted:
            continue
        static, _has_static = jitted[last]
        for pos, arg in enumerate(node.args):
            if isinstance(arg, ast.Starred):
                break  # positions unknowable past a *splat
            if pos in static and not _is_constantish(arg):
                findings.append(make_finding(
                    src, "SWL202", arg,
                    f"static argument {pos} of jit-wrapped `{last}` is not "
                    f"a constant — every distinct value compiles a new "
                    f"variant"))
            elif isinstance(arg, ast.JoinedStr):
                findings.append(make_finding(
                    src, "SWL202", arg,
                    f"f-string argument to jit-wrapped `{last}` — a "
                    f"distinct (static, hashed-by-value) string per call "
                    f"recompiles per call"))
            elif (isinstance(arg, ast.Call)
                    and dotted_name(arg.func) == "len"):
                findings.append(make_finding(
                    src, "SWL202", arg,
                    f"`len(...)` scalar passed to jit-wrapped `{last}` — "
                    f"per-call Python scalars churn weak types (and shape-"
                    f"deriving uses recompile); pass a fixed-shape array "
                    f"or bucket it"))
            elif pos in static and isinstance(arg, ast.Dict):
                findings.append(make_finding(
                    src, "SWL202", arg,
                    f"dict display in static position {pos} of `{last}` — "
                    f"hash depends on insertion order; use a frozen/sorted "
                    f"structure"))
    return findings


# ------------------------------------------------------------------ SWL204

# constructors whose FIRST argument is (or contains) the result shape
_ARRAY_CTORS = ("zeros", "ones", "full", "empty")


def _shape_has_len(node: ast.AST) -> bool:
    return any(isinstance(n, ast.Call) and dotted_name(n.func) == "len"
               for n in ast.walk(node))


def _is_len_shaped_ctor(node: ast.AST) -> bool:
    """``np.zeros((len(x), K))``-style: an array constructor whose shape
    expression embeds a runtime ``len(...)``."""
    if not (isinstance(node, ast.Call) and node.args):
        return False
    name = dotted_name(node.func)
    if not name or name.split(".")[-1] not in _ARRAY_CTORS:
        return False
    return _shape_has_len(node.args[0])


def _check_len_shaped_args(src: SourceFile) -> List[Finding]:
    """SWL204: len()-shaped host arrays reaching jitted callables. Scope
    is per-function: a direct constructor argument, or a local name bound
    to such a constructor earlier in the same function (one hop — the
    pattern both PROFILE r4 mines took)."""
    findings: List[Finding] = []
    jitted = _collect_jitted(src)
    if not jitted:
        return findings
    fns = [n for n in ast.walk(src.tree)
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]
    for fn in fns:
        # one-hop local bindings: name -> the len-shaped ctor node
        mined: Dict[str, ast.AST] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) \
                    and _is_len_shaped_ctor(node.value):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        mined[tgt.id] = node.value
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            cname = dotted_name(node.func)
            if cname is None or cname.split(".")[-1] not in jitted:
                continue
            for arg in node.args:
                if isinstance(arg, ast.Starred):
                    break
                # report at the MINE (the constructor), not the call:
                # that's the line to pad/bucket
                via = None
                if _is_len_shaped_ctor(arg):
                    via = arg
                elif isinstance(arg, ast.Name) and arg.id in mined:
                    via = mined[arg.id]
                if via is not None:
                    findings.append(make_finding(
                        src, "SWL204", via,
                        f"argument of jit-wrapped "
                        f"`{cname.split('.')[-1]}` has a len()-derived "
                        f"shape — every distinct count is a fresh traced "
                        f"shape (a compile mine); pad to a fixed wave "
                        f"size or bucket the count"))
    return findings


# ------------------------------------------------------------------ SWL205

def _is_len_or_shape_expr(node: ast.AST) -> bool:
    """``len(x)`` or ``x.shape`` / ``x.shape[i]`` — descriptor math that
    turns data into a traced dimension."""
    if isinstance(node, ast.Call) and dotted_name(node.func) == "len":
        return True
    if isinstance(node, ast.Subscript):
        node = node.value
    return isinstance(node, ast.Attribute) and node.attr == "shape"


def _check_descriptor_shape_math(src: SourceFile) -> List[Finding]:
    """SWL205: in HOT functions, a scalar local bound to len()/.shape
    descriptor math that then shapes an array constructor reaching a
    jit-wrapped callable (directly or through a one-hop array binding).
    SWL204 catches ``np.zeros((len(x), K))`` spelled inline; this is the
    laundered form — ``n = len(stream); np.zeros(n)`` — which is exactly
    how a ragged dispatch path accidentally keys its compiled-variant
    space on per-wave token counts. A bucketing call
    (``self._ragged_width_for(len(stream))``) breaks the taint: the
    result is a method value, not descriptor math."""
    findings: List[Finding] = []
    jitted = _collect_jitted(src)
    if not jitted:
        return findings
    hot_fns = [n for n in ast.walk(src.tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
               and src.is_hot(n)]
    for fn in hot_fns:
        tainted: Set[str] = set()
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Assign)
                    and _is_len_or_shape_expr(node.value)):
                continue
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    tainted.add(tgt.id)
                elif isinstance(tgt, ast.Tuple):
                    # unpacking: W, Hq = q.shape — every bound name is
                    # a traced dimension
                    for elt in tgt.elts:
                        if isinstance(elt, ast.Name):
                            tainted.add(elt.id)
        if not tainted:
            continue

        def _shape_uses_taint(sh: ast.AST) -> bool:
            return any(isinstance(n, ast.Name) and n.id in tainted
                       for n in ast.walk(sh))

        def _is_tainted_ctor(node: ast.AST) -> bool:
            if not (isinstance(node, ast.Call) and node.args):
                return False
            name = dotted_name(node.func)
            if not name or name.split(".")[-1] not in _ARRAY_CTORS:
                return False
            return _shape_uses_taint(node.args[0])

        mined: Dict[str, ast.AST] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) and _is_tainted_ctor(node.value):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        mined[tgt.id] = node.value
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            cname = dotted_name(node.func)
            if cname is None or cname.split(".")[-1] not in jitted:
                continue
            for arg in node.args:
                if isinstance(arg, ast.Starred):
                    break
                via = None
                if _is_tainted_ctor(arg):
                    via = arg
                elif isinstance(arg, ast.Name) and arg.id in mined:
                    via = mined[arg.id]
                if via is not None:
                    findings.append(make_finding(
                        src, "SWL205", via,
                        f"argument of jit-wrapped "
                        f"`{cname.split('.')[-1]}` is shaped by "
                        f"descriptor len()/.shape math in hot dispatch "
                        f"code — every distinct count compiles a new "
                        f"variant; quantize the width through the "
                        f"engine's ladder (e.g. _ragged_width_for / "
                        f"_rows_for) instead"))
    return findings


# ------------------------------------------------------------------ SWL203

def _check_warmup_coverage(src: SourceFile) -> List[Finding]:
    findings: List[Finding] = []
    for cls in ast.walk(src.tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        methods = {n.name: n for n in cls.body
                   if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        warm = [methods[m] for m in WARMUP_METHODS if m in methods]
        if not warm:
            continue
        # class namespace = methods + class-level assignment targets
        # (e.g. the mirrored-call table binding methods by bare name)
        class_names: Set[str] = set(methods)
        for item in cls.body:
            if isinstance(item, ast.Assign):
                for tgt in item.targets:
                    tname = dotted_name(tgt)
                    if tname:
                        class_names.add(tname.split(".")[-1])
        # jit-assigned attributes anywhere in the class (incl. __init__
        # bodies), and name->RHS-references for the reachability closure.
        # Only self-attribute and class-level targets participate —
        # method locals would bridge unrelated call graphs.
        jit_attrs: Dict[str, ast.AST] = {}
        assign_refs: Dict[str, Set[str]] = {}
        class_level = set(map(id, cls.body))
        for node in ast.walk(cls):
            if not isinstance(node, ast.Assign):
                continue
            refs = _ref_names(node.value, class_names)
            is_jit = (isinstance(node.value, ast.Call)
                      and _is_jit_call(node.value))
            for tgt in node.targets:
                is_self_attr = (isinstance(tgt, ast.Attribute)
                                and isinstance(tgt.value, ast.Name)
                                and tgt.value.id == "self")
                if not is_self_attr and id(node) not in class_level:
                    continue
                tname = dotted_name(tgt)
                if tname is None:
                    continue
                last = tname.split(".")[-1]
                assign_refs.setdefault(last, set()).update(refs)
                if is_jit:
                    jit_attrs[last] = node
        if not jit_attrs:
            continue
        method_refs = {name: _ref_names(fn, class_names)
                       for name, fn in methods.items()}
        reachable: Set[str] = set()
        frontier: Set[str] = set()
        for fn in warm:
            frontier |= _ref_names(fn, class_names)
        while frontier:
            new: Set[str] = set()
            for name in frontier:
                if name in reachable:
                    continue
                reachable.add(name)
                new |= method_refs.get(name, set())
                new |= assign_refs.get(name, set())
            frontier = new - reachable
        for attr, node in sorted(jit_attrs.items()):
            if attr not in reachable:
                findings.append(make_finding(
                    src, "SWL203", node,
                    f"jit entry point `{attr}` of class `{cls.name}` is "
                    f"not reachable from its warmup call plan — the first "
                    f"serving-path call pays a cold compile mid-traffic"))
    return findings
