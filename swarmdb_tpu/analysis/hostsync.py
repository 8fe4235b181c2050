"""host-sync checks (SWL101/SWL102/SWL105).

The engine's throughput contract is "one host sync per decode chunk"
(backend/engine.py module docstring): every synchronous fetch stalls the
dispatch thread for a device round-trip, so a stray ``device_get`` or
``.item()`` in the dispatch path caps the whole engine regardless of batch
size. The
contract used to live in comments only; here it is machine-checked for
every function annotated hot (``# swarmlint: hot`` or an ``@hot``
decorator).

- SWL101: calls that ARE a host sync — ``jax.device_get``,
  ``jax.block_until_ready``, ``<x>.block_until_ready()``. Flagged
  unconditionally inside hot functions (the engine's one sanctioned sync
  carries an inline ``disable`` with its justification).
- SWL102: host materialization of a *device* value — ``.item()`` /
  ``.tolist()`` / ``np.asarray`` / ``np.array`` / ``jnp.asarray`` /
  ``jax.device_put`` / ``float()`` / ``int()`` — flagged only when the
  operand is device-tainted: assigned from a ``jax.*``/``jnp.*`` call or a
  known jit-wrapped callable in the same function, or a ``self.<attr>``
  declared ``# swarmlint: device-state``. Plain numpy-on-host work (the
  admission path builds its dispatch arguments with numpy on purpose —
  the transfer rides the jit call) is NOT flagged.
- SWL105: a host sync lexically inside a ``for``/``while`` loop in hot
  code — a per-ITERATION sync, the exact shape the device-resident
  decode loop (engine emission ring, ISSUE 8) exists to remove. The
  ``# swarmlint: sanctioned-drain`` marker (same line, or a comment
  line directly above) declares a legitimate straight-line per-request
  drain and quiets SWL101 there; it NEVER applies inside a loop — a
  drain you loop over is a per-chunk sync wearing a costume, and stays
  an SWL105 finding.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional, Set, Tuple

from .core import Finding, SourceFile, dotted_name, make_finding

SYNC_CALLS = {"jax.device_get", "jax.block_until_ready"}
MATERIALIZE_CALLS = {
    "np.asarray", "np.array", "numpy.asarray", "numpy.array",
    "jnp.asarray", "jax.device_put", "float", "int",
}
MATERIALIZE_METHODS = {"item", "tolist"}
# call results that produce device values (taint sources)
DEVICE_PREFIXES = ("jax.", "jnp.", "jax.numpy.")
# call results that are explicitly host-side (taint sinks)
HOST_CALLS = {"jax.device_get", "np.asarray", "np.array", "numpy.asarray",
              "numpy.array"}


def _collect_jitted_names(tree: ast.Module) -> Set[str]:
    """Last-segment names of callables wrapped by jax.jit/pmap/shard_map
    anywhere in the module — calling one returns device arrays."""
    out: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign):
            continue
        name = dotted_name(node.value)
        if name is None:
            continue
        last = name.split(".")[-1]
        if last in ("jit", "pmap", "shard_map"):
            for tgt in node.targets:
                tname = dotted_name(tgt)
                if tname:
                    out.add(tname.split(".")[-1])
    return out


def _device_state_of(src: SourceFile) -> Dict[ast.ClassDef, Set[str]]:
    out: Dict[ast.ClassDef, Set[str]] = {}
    for line, names in src.directives.device_state:
        cls = src.enclosing_scope(line, classes_only=True)
        if isinstance(cls, ast.ClassDef):
            out.setdefault(cls, set()).update(names)
    return out


class _Taint:
    """Flow-insensitive per-function taint: names assigned from device-
    producing calls are device values; names assigned from device_get /
    np.asarray are host values (host wins — de-tainting is explicit)."""

    def __init__(self, fn: ast.AST, jitted: Set[str],
                 device_attrs: Set[str]) -> None:
        self.device: Set[str] = set()
        self.host: Set[str] = set()
        self.device_attrs = device_attrs
        self.jitted = jitted
        for node in ast.walk(fn):
            if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                value = node.value
                if value is None:
                    continue
                targets = (node.targets if isinstance(node, ast.Assign)
                           else [node.target])
                names = []
                for t in targets:
                    elts = t.elts if isinstance(t, ast.Tuple) else [t]
                    names.extend(e.id for e in elts
                                 if isinstance(e, ast.Name))
                if self._is_host_producer(value):
                    self.host.update(names)
                elif self._is_device_producer(value):
                    self.device.update(names)

    def _call_name(self, node: ast.AST) -> Optional[str]:
        return dotted_name(node) if isinstance(node, ast.Call) else None

    def _is_host_producer(self, value: ast.AST) -> bool:
        return self._call_name(value) in HOST_CALLS

    def _is_device_producer(self, value: ast.AST) -> bool:
        name = self._call_name(value)
        if name is None:
            return False
        if name in HOST_CALLS:
            return False
        if name.startswith(DEVICE_PREFIXES):
            return True
        return name.split(".")[-1] in self.jitted

    def tainted(self, expr: ast.AST) -> bool:
        """Is ``expr`` plausibly a device value?"""
        if isinstance(expr, ast.Name):
            if expr.id in self.host:
                return False
            return expr.id in self.device
        if isinstance(expr, ast.Attribute):
            if (isinstance(expr.value, ast.Name)
                    and expr.value.id == "self"):
                return expr.attr in self.device_attrs
            return False
        if isinstance(expr, ast.Subscript):
            return self.tainted(expr.value)
        if isinstance(expr, ast.Call):
            return self._is_device_producer(expr)
        if isinstance(expr, (ast.Tuple, ast.List)):
            return any(self.tainted(e) for e in expr.elts)
        return False


SANCTIONED_DRAIN_RE = None  # compiled lazily (keep import surface tiny)


def _sanctioned_lines(src: SourceFile) -> Set[int]:
    """Code lines covered by a ``# swarmlint: sanctioned-drain`` marker:
    the marker's own line (inline form), or — when the marker opens a
    standalone comment block — the first code line after the block."""
    import re

    global SANCTIONED_DRAIN_RE
    if SANCTIONED_DRAIN_RE is None:
        SANCTIONED_DRAIN_RE = re.compile(
            r"#\s*swarmlint:\s*sanctioned-drain\b")
    out: Set[int] = set()
    for idx, line in enumerate(src.lines):
        if not SANCTIONED_DRAIN_RE.search(line):
            continue
        lineno = idx + 1
        out.add(lineno)
        if line.lstrip().startswith("#"):
            # standalone comment: sanction the first code line below
            j = idx + 1
            while j < len(src.lines):
                stripped = src.lines[j].strip()
                if stripped and not stripped.startswith("#"):
                    out.add(j + 1)
                    break
                j += 1
    return out


def _loop_spans(fn: ast.AST) -> List[Tuple[int, int]]:
    """(first, last) line spans of every loop BODY inside ``fn`` (the
    header line is excluded so `for x in jax.device_get(...)` — a
    one-time pre-loop sync — stays SWL101 territory)."""
    spans: List[Tuple[int, int]] = []
    for node in ast.walk(fn):
        if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
            body = list(node.body) + list(node.orelse)
            if body:
                last = max(getattr(b, "end_lineno", b.lineno)
                           for b in body)
                spans.append((body[0].lineno, last))
    return spans


def check(src: SourceFile) -> List[Finding]:
    findings: List[Finding] = []
    jitted = _collect_jitted_names(src.tree)
    device_state = _device_state_of(src)
    sanctioned = _sanctioned_lines(src)

    # (hot function, enclosing class) pairs, hotness propagated into
    # nested defs
    hot_fns: List[Tuple[ast.AST, Optional[ast.ClassDef]]] = []

    def visit(node: ast.AST, hot: bool, cls: Optional[ast.ClassDef]) -> None:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, ast.ClassDef):
                visit(child, hot, child)
            elif isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                child_hot = hot or src.is_hot(child)
                if child_hot:
                    hot_fns.append((child, cls))
                visit(child, child_hot, cls)
            else:
                visit(child, hot, cls)

    visit(src.tree, False, None)

    seen_lines: Set[int] = set()
    for fn, cls in hot_fns:
        attrs = device_state.get(cls, set()) if cls is not None else set()
        taint = _Taint(fn, jitted, attrs)
        loops = _loop_spans(fn)

        def _in_loop(lineno: int) -> bool:
            return any(lo <= lineno <= hi for lo, hi in loops)

        def _sync_finding(node: ast.AST, what: str) -> Optional[Finding]:
            if _in_loop(node.lineno):
                return make_finding(
                    src, "SWL105", node,
                    f"{what} inside a LOOP in hot function `{fn.name}` — "
                    f"a per-iteration host sync; fold the loop on-device "
                    f"(lax.while_loop + emission ring) or drain once "
                    f"outside it")
            if node.lineno in sanctioned:
                return None  # declared per-request drain, straight-line
            return make_finding(
                src, "SWL101", node,
                f"{what} inside hot function `{fn.name}` — every sync "
                f"here serializes the decode pipeline (mark a legitimate "
                f"per-request drain with `# swarmlint: sanctioned-drain`)")

        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            key = (node.lineno, node.col_offset)
            if key in seen_lines:
                continue
            name = dotted_name(node.func)
            if name in SYNC_CALLS:
                seen_lines.add(key)
                f = _sync_finding(node, f"`{name}`")
                if f is not None:
                    findings.append(f)
                continue
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "block_until_ready"):
                seen_lines.add(key)
                f = _sync_finding(node, "`.block_until_ready()`")
                if f is not None:
                    findings.append(f)
                continue
            if (isinstance(node.func, ast.Attribute)
                    and node.func.attr in MATERIALIZE_METHODS
                    and taint.tainted(node.func.value)):
                seen_lines.add(key)
                findings.append(make_finding(
                    src, "SWL102", node,
                    f"`.{node.func.attr}()` on a device value inside hot "
                    f"function `{fn.name}` forces a host transfer"))
                continue
            if (name in MATERIALIZE_CALLS and node.args
                    and taint.tainted(node.args[0])):
                seen_lines.add(key)
                findings.append(make_finding(
                    src, "SWL102", node,
                    f"`{name}(...)` materializes a device value on the "
                    f"host inside hot function `{fn.name}`"))
    return findings
