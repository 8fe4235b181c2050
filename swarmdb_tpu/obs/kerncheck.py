"""Runtime Pallas kernel sanitizer — the dynamic half of swarmkern
(ISSUE 16; static half: analysis/kernelcheck.py).

The static pass proves what it can about ``pl.pallas_call`` sites from
the AST: block bounds over the grid, output-coverage, VMEM budgets.
It deliberately goes quiet wherever an index map or a store guard is
DATA-dependent (page tables, ragged descriptors) — exactly the part a
paged-attention kernel lives on. This module owns that remainder: when
``SWARMDB_KERNCHECK=1``, the TPU-gated dispatchers in ``ops/layers.py``
/ ``ops/paged_kv.py`` come from checked factories that shadow every
concrete (non-traced) call through a **host-side grid interpreter**
over the real kernel function:

- every Ref the kernel touches is a bounds-checked numpy-backed
  stand-in (:class:`ShadowRef`): an out-of-range block or ``pl.ds``
  slice is a violation naming the offending *grid coordinate* and the
  slice, instead of the silent clamp TPU hardware performs,
- the output buffer is pre-poisoned with a canary (``CANARY``); after
  the grid completes, every row a descriptor declares live must have
  been overwritten — surviving canary is a ``short-write`` violation
  (the runtime face of SWL905),
- per grid step the interpreter diffs the output block: an element
  changed by two different outer grid rows (the zeros the first cell to
  visit a block leaves exempt — the zero-fill idiom) is a ``write-race``
  violation naming both writers (the runtime face of SWL902),
- a kernel that walks its own keys inside a grid step (the ragged
  prefill kernel: pools in ``ANY`` space, ``make_async_copy`` into a
  double buffer, ``fori_loop`` trips read from SMEM) runs the same way:
  its loops as Python loops, its copies as bounds-checked numpy copies
  that land at ``wait()``, its buffers starting as NaN,
- the shadow result is compared against the dispatched result — a
  free differential check of kernel-vs-dispatch parity on the live
  descriptors; :func:`differential_ragged_prefill` runs the same
  comparison over randomized descriptor soups (mixed lens,
  page-boundary crossings, empty rows, split rows) for the CI harness,
- the chunked paged DECODE kernel, the one the engine runs
  (``paged_decode_gqa_attention_chunked`` and its int8 twin), has no
  numpy replay of its walk (rows and page blocks from SMEM, a double
  buffer that runs across rows): it is held to the XLA gather form
  instead. :func:`differential_paged_decode` runs the kernels
  interpreted against ``gqa_attention_chunked`` on the gathered view
  (mixed lengths, empty slots, a live-row list that leaves slots out,
  a chunk buffer part full), and the checked dispatcher
  (:func:`checked_paged_attention_dispatch_chunked`) compares the
  kernel's live rows with that form on every concrete call and holds
  the kernel to its contract that a slot not on the list is exact
  zeros; where the dispatch itself gathered it runs the kernel
  interpreted on the same operands.

Violations are recorded once, written to attached flight recorders as
``kerncheck.violation`` instants, dumped immediately to
``kerncheck_<node>.json`` in ``SWARMDB_FLIGHT_DIR`` (a SIGKILLed chaos
victim never reaches atexit), surfaced at ``GET /admin/kerncheck``,
and exported on ``/metrics`` as ``swarmdb_kernel_violations_total`` —
the same contract as lockcheck/pagecheck.

With the flag off (default) the checked factories return the plain
dispatch functions UNTOUCHED (type identity pinned by
tests/test_kernelcheck.py) and this module is never imported on the
serving path.

The registry's mutex is a *leaf* lock: no user code runs under it.
The pallas-shim patch lock (``_PATCH_MU``) serializes shadow runs —
``pl.program_id``/``pl.num_programs``/``pl.when``/``pl.ds``,
``jax.lax.fori_loop`` and ``pltpu.make_async_copy`` are module
attributes the kernels resolve at call time, so the interpreter swaps
them for concrete evaluators for the duration of a run.
"""

from __future__ import annotations

import atexit
import contextlib
import functools
import json
import logging
import os
import re
import threading
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

logger = logging.getLogger("swarmdb_tpu.obs")

__all__ = ["enabled", "registry", "KernCheckRegistry", "ShadowRef",
           "CANARY", "parity_tol",
           "ragged_prefill_body", "shadow_ragged_prefill",
           "shadow_paged_write_ragged", "check_wave_descriptors",
           "differential_ragged_prefill", "differential_paged_decode",
           "checked_ragged_prefill_dispatch",
           "checked_paged_attention_dispatch_chunked",
           "checked_ssm_state_read", "checked_paged_write_ragged"]

# float canary pre-poisoning shadow outputs: exactly representable in
# bf16/f32 and far outside attention's output range (softmax-weighted
# averages of unit-scale values), so surviving canary == never written
CANARY = -16384.0

# parity tolerance between the shadow fold (fp32 online softmax) and
# the dispatched path (kernel or dense reference): both accumulate in
# fp32 but tile reductions differently; bf16 outputs round to ~1e-2
_PARITY_TOL = 2e-2

# int8 pools: the shadow dequantizes at the boundary while the kernel
# dequantizes per-tile (same values, different mult order), and every
# scale product rounds through bf16 once more — a hair looser
_PARITY_TOL_INT8 = 6e-2


def parity_tol(dtype_name: Optional[str] = None) -> float:
    """Shadow-vs-dispatch tolerance for the ACTIVE pool dtype
    (``SWARMDB_KV_DTYPE``); pass ``dtype_name`` to override."""
    if dtype_name is None:
        from ..ops.paged_kv import kv_dtype_name

        try:
            dtype_name = kv_dtype_name()
        except ValueError:
            dtype_name = "bf16"
    return _PARITY_TOL_INT8 if dtype_name == "int8" else _PARITY_TOL


def _dequant_pools(k_pages, v_pages):
    """QuantPool -> plain f32 pools (identity on plain arrays): the
    shadow interpreter runs the full-precision kernel on boundary-
    dequantized pages — the same values the quant kernel produces
    in-tile, so parity still binds the dispatched path."""
    from ..ops.paged_kv import _dequantize_pages, is_quantized

    if is_quantized(k_pages):
        k_pages = _dequantize_pages(k_pages.data, k_pages.scale)
        v_pages = _dequantize_pages(v_pages.data, v_pages.scale)
    return k_pages, v_pages


def enabled() -> bool:
    return os.environ.get("SWARMDB_KERNCHECK", "0") not in ("", "0")


def _max_shadow_width() -> int:
    """Shadow runs cost O(grid * block) host work — bound the packed
    width they chase so a production-sized wave doesn't stall serving."""
    try:
        return int(os.environ.get("SWARMDB_KERNCHECK_MAX_W", "512"))
    except ValueError:
        return 512


def _short_stack(skip: int = 3, limit: int = 5) -> List[str]:
    out = []
    for fr in reversed(traceback.extract_stack()[:-skip]):
        if fr.filename.endswith(("kerncheck.py",)):
            continue
        out.append(f"{os.path.basename(fr.filename)}:{fr.lineno} "
                   f"{fr.name}")
        if len(out) >= limit:
            break
    return out


# violation kind -> the static rule it is the runtime face of
_KIND_RULE = {
    "oob-block": "SWL901",
    "oob-ref": "SWL901",
    "write-race": "SWL902",
    "short-write": "SWL905",
}


class KernCheckRegistry:
    """Process-global kernel-sanitizer state (violations + check tallies)."""

    def __init__(self) -> None:
        # leaf lock: no user code runs under it
        self._mu = threading.Lock()
        self._violations: List[Dict[str, Any]] = []
        self._violation_keys: set = set()
        self._checks: Dict[str, int] = {}
        self._flights: List[Any] = []
        self._atexit_armed = False

    # ------------------------------------------------------------ wiring

    def attach_flight(self, recorder: Any) -> None:
        with self._mu:
            if recorder not in self._flights:
                self._flights.append(recorder)
            if not self._atexit_armed:
                self._atexit_armed = True
                atexit.register(self._atexit_dump)

    def note_check(self, check: str) -> None:
        """Tally one shadow pass (coverage evidence for the report)."""
        with self._mu:
            self._checks[check] = self._checks.get(check, 0) + 1

    # ----------------------------------------------------------- events

    def record(self, kind: str, kernel: str, message: str,
               where: Optional[Dict[str, Any]] = None) -> None:
        """Record one violation (dedup by kind/kernel/site) and emit the
        side effects (flight instants, immediate dump) OUTSIDE the
        mutex."""
        key = (kind, kernel, str(sorted((where or {}).items()))[:160])
        with self._mu:
            if key in self._violation_keys:
                return
            self._violation_keys.add(key)
            v = {
                "kind": kind,
                "rule": _KIND_RULE.get(kind),
                "kernel": kernel,
                "message": message,
                "where": dict(where or {}),
                "thread": threading.current_thread().name,
                "stack": _short_stack(),
                "detected_at": time.time(),
            }
            self._violations.append(v)
        self._emit(v)

    def _emit(self, violation: Dict[str, Any]) -> None:
        logger.warning("kerncheck: %s violation in %s: %s",
                       violation["kind"], violation["kernel"],
                       violation["message"])
        # swarmlint: disable=SWL303 -- benign racy snapshot of an append-only list: flight rings take their own locks, so iterating under _mu would re-enter
        for fl in list(self._flights):
            try:
                fl.record_event({
                    "kind": "kerncheck.violation",
                    "ts": time.time(),
                    "violation_kind": violation["kind"],
                    "kernel": violation["kernel"],
                    "rule": violation["rule"],
                })
            except Exception:
                pass
        directory = os.environ.get("SWARMDB_FLIGHT_DIR")
        if directory:
            try:
                self.dump_to(directory)
            except Exception:
                logger.exception("kerncheck dump failed")

    # ------------------------------------------------------------ reading

    def _node_identity(self) -> str:
        raw = (os.environ.get("SWARMDB_NODE_ID") or f"p{os.getpid()}")
        return re.sub(r"[^A-Za-z0-9_.-]", "_", raw)

    def violations(self) -> List[Dict[str, Any]]:
        with self._mu:
            return [dict(v) for v in self._violations]

    def report(self) -> Dict[str, Any]:
        with self._mu:
            violations = [dict(v) for v in self._violations]
            checks = dict(self._checks)
        return {
            "enabled": enabled(),
            "node": self._node_identity(),
            "checks": checks,
            "violations": violations,
            "generated_at": time.time(),
        }

    def prometheus_lines(self, prefix: str = "swarmdb_") -> List[str]:
        with self._mu:
            n = len(self._violations)
            checks = dict(self._checks)
        lines = [f"# TYPE {prefix}kernel_violations_total counter",
                 f"{prefix}kernel_violations_total {n}",
                 f"# TYPE {prefix}kernel_checks_total counter"]
        for k in sorted(checks):
            lines.append(
                f'{prefix}kernel_checks_total{{check="{k}"}} {checks[k]}')
        return lines

    def dump_to(self, directory: str) -> str:
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(
            directory, f"kerncheck_{self._node_identity()}.json")
        tmp = f"{path}.{os.getpid()}.tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(self.report(), f, indent=1)
        os.replace(tmp, path)
        return path

    def _atexit_dump(self) -> None:
        directory = os.environ.get("SWARMDB_FLIGHT_DIR")
        if not directory:
            return
        try:
            self.dump_to(directory)
        except Exception:  # pragma: no cover - shutdown best-effort
            pass

    def reset(self) -> None:
        """Tests only — forget violations, tallies, and flights."""
        with self._mu:
            self._violations.clear()
            self._violation_keys.clear()
            self._checks.clear()
            self._flights.clear()


_REGISTRY = KernCheckRegistry()


def registry() -> KernCheckRegistry:
    return _REGISTRY


# ------------------------------------------------------ shadow machinery

# serializes shadow runs: the interpreter swaps pl.program_id /
# pl.num_programs / pl.when / pl.ds / lax.fori_loop / make_async_copy
# for concrete evaluators while a kernel body executes on the host
_PATCH_MU = threading.RLock()


@contextlib.contextmanager
def _patched_pallas(state: Dict[str, Any]):
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    with _PATCH_MU:
        saved = (pl.program_id, pl.num_programs, pl.when, pl.ds,
                 jax.lax.fori_loop, pltpu.make_async_copy)
        real_fori_loop = saved[4]

        def _program_id(i: int) -> int:
            return state["coords"][i]

        def _num_programs(i: int) -> int:
            return state["grid"][i]

        def _when(cond):
            def deco(fn):
                if bool(cond):
                    fn()
                return fn
            return deco

        def _ds(start, size):
            return slice(int(start), int(start) + int(size))

        def _fori_loop(lower, upper, body, init, **kw):
            # a kernel's in-step loops (trip counts read from SMEM) run
            # as Python loops over the shadow refs; anything traced is
            # somebody else's loop and keeps the real one
            try:
                lo, hi = int(lower), int(upper)
            except Exception:
                return real_fori_loop(lower, upper, body, init, **kw)
            val = init
            for i in range(lo, hi):
                val = body(i, val)
            return val

        pl.program_id = _program_id
        pl.num_programs = _num_programs
        pl.when = _when
        pl.ds = _ds
        jax.lax.fori_loop = _fori_loop
        pltpu.make_async_copy = _ShadowCopy
        try:
            yield
        finally:
            (pl.program_id, pl.num_programs, pl.when, pl.ds,
             jax.lax.fori_loop, pltpu.make_async_copy) = saved


class _ShadowCopy:
    """Stand-in for ``pltpu.make_async_copy(src, dst, sem)`` over shadow
    refs. The bytes land at ``wait()``, not at ``start()``: a kernel that
    reads its buffer before waiting reads what was there before, and the
    parity check sees it."""

    def __init__(self, src: "ShadowRef", dst: "ShadowRef", sem: Any) -> None:
        self._src, self._dst = src, dst

    def start(self) -> None:
        pass

    def wait(self) -> None:
        self._dst[...] = self._src[...]


class ShadowRef:
    """Bounds-checked numpy-backed stand-in for a pallas Ref. Every
    index (int, slice, ``pl.ds``) is validated against the block shape;
    out-of-range access records an ``oob-ref`` violation naming the
    current grid coordinate and the slice, then clamps so the shadow
    run can finish and surface everything at once."""

    def __init__(self, arr: np.ndarray, name: str, kernel: str,
                 state: Dict[str, Any]) -> None:
        self._arr = arr
        self._name = name
        self._kernel = kernel
        self._state = state

    @property
    def shape(self) -> Tuple[int, ...]:
        return self._arr.shape

    @property
    def dtype(self):
        return self._arr.dtype

    def __jax_array__(self):
        # jnp.zeros_like(acc_ref) etc. inside kernel bodies
        import jax.numpy as jnp

        return jnp.asarray(np.asarray(self._arr))

    def _resolve(self, idx: Any) -> Tuple[Any, ...]:
        if not isinstance(idx, tuple):
            idx = (idx,)
        if any(v is Ellipsis for v in idx):
            k = next(i for i, v in enumerate(idx) if v is Ellipsis)
            fill = len(self._arr.shape) - (len(idx) - 1)
            idx = idx[:k] + (slice(None),) * fill + idx[k + 1:]
        out: List[Any] = []
        for ax, v in enumerate(idx):
            dim = self._arr.shape[ax]
            if isinstance(v, slice):
                start = 0 if v.start is None else int(v.start)
                stop = dim if v.stop is None else int(v.stop)
                if start < 0 or stop > dim:
                    self._oob(ax, f"[{start}:{stop})", dim)
                    start = max(0, min(start, dim))
                    stop = max(start, min(stop, dim))
                out.append(slice(start, stop))
            else:
                i = int(v)
                if not 0 <= i < dim:
                    self._oob(ax, str(i), dim)
                    i = max(0, min(i, dim - 1))
                out.append(i)
        return tuple(out)

    def _oob(self, axis: int, what: str, dim: int) -> None:
        coords = tuple(self._state.get("coords", ()))
        registry().record(
            "oob-ref", self._kernel,
            f"ref '{self._name}' axis {axis} index {what} outside "
            f"[0,{dim}) at grid cell {coords} — the kernel would read or "
            f"write past its block (TPU clamps silently; this is the "
            f"runtime face of SWL901)",
            {"ref": self._name, "axis": axis, "grid": list(coords),
             "slice": what})

    @property
    def at(self) -> "_ShadowAt":
        """``ref.at[idx]``: a bounds-checked sub-ref over the same
        backing store (what a DMA descriptor is built from)."""
        return _ShadowAt(self)

    def __getitem__(self, idx: Any):
        import jax.numpy as jnp

        # a copy: a load is a value, and jnp.asarray may alias numpy memory
        return jnp.asarray(np.array(self._arr[self._resolve(idx)]))

    def __setitem__(self, idx: Any, value: Any) -> None:
        s = self._resolve(idx)
        self._arr[s] = np.asarray(value, dtype=self._arr.dtype)


class _ShadowAt:
    def __init__(self, ref: ShadowRef) -> None:
        self._ref = ref

    def __getitem__(self, idx: Any) -> ShadowRef:
        r = self._ref
        return ShadowRef(r._arr[r._resolve(idx)], r._name, r._kernel,
                         r._state)


def _run_grid(kernel: Callable, kernel_name: str,
              grid: Tuple[int, ...],
              scalars: Sequence[Tuple[str, np.ndarray]],
              inputs: Sequence[Tuple[str, np.ndarray, Tuple[int, ...],
                                     Callable]],
              out: Tuple[str, np.ndarray, Tuple[int, ...], Callable],
              scratch: Sequence[np.ndarray],
              writer_axes: Optional[int] = None,
              ) -> Tuple[np.ndarray, np.ndarray]:
    """Interpret ``kernel`` over ``grid`` (row-major, last axis minor —
    the TPU order) against numpy backing stores with bounds-checked
    block selection, recording oob-block / oob-ref / write-race
    violations as it goes. ``writer_axes`` leading grid axes name a
    writer (default: all but the minor one, along which a kernel
    accumulates into its own block). Returns (output backing store,
    per-element last-writer map: -1 = only ever touched by its block's
    init cell)."""
    if writer_axes is None:
        writer_axes = len(grid) - 1
    reg = registry()
    state: Dict[str, Any] = {"grid": grid, "coords": (0,) * len(grid)}
    scalar_refs = [ShadowRef(arr, name, kernel_name, state)
                   for name, arr in scalars]
    out_name, out_buf, out_bs, out_map = out
    scratch_refs = [ShadowRef(arr, f"scratch{i}", kernel_name, state)
                    for i, arr in enumerate(scratch)]
    # element-granular last-changer for the race check: -1 = untouched
    last_writer = np.full(out_buf.shape, -1, np.int64)
    visited: set = set()  # output blocks some grid cell has mapped

    def block_view(name: str, arr: np.ndarray, bs: Tuple[int, ...],
                   idx: Sequence[Any]) -> Tuple[np.ndarray,
                                                Tuple[slice, ...]]:
        slices: List[slice] = []
        for ax, (i, b) in enumerate(zip(idx, bs)):
            start = int(i) * b
            if start < 0 or start + b > arr.shape[ax]:
                reg.record(
                    "oob-block", kernel_name,
                    f"operand '{name}' block axis {ax}: index map "
                    f"selected [{start},{start + b}) outside "
                    f"[0,{arr.shape[ax]}) at grid cell "
                    f"{tuple(state['coords'])} — an out-of-bounds page "
                    f"id or block index (runtime face of SWL901)",
                    {"operand": name, "axis": ax,
                     "grid": list(state["coords"]),
                     "slice": f"[{start},{start + b})"})
                start = max(0, min(start, arr.shape[ax] - b))
            slices.append(slice(start, start + b))
        t = tuple(slices)
        return arr[t], t

    with _patched_pallas(state):
        for coords in np.ndindex(*grid):
            state["coords"] = coords
            in_refs = []
            for name, arr, bs, imap in inputs:
                idx = imap(*coords, *scalar_refs)
                view, _ = block_view(name, arr, bs, idx)
                in_refs.append(ShadowRef(view, name, kernel_name, state))
            oidx = out_map(*coords, *scalar_refs)
            oview, oslices = block_view(out_name, out_buf, out_bs, oidx)
            pre = oview.copy()
            kernel(*scalar_refs, *in_refs,
                   ShadowRef(oview, out_name, kernel_name, state),
                   *scratch_refs)
            changed = np.asarray(pre != oview)
            # the zeros the FIRST cell to visit an output block leaves
            # are the zero-fill init idiom — exempt from writer tracking
            # so a later per-row finalize is not a "race" against them
            # and a row they alone touched still counts as unwritten.
            # What that cell writes besides (real, non-zero values: its
            # own row's finalize) is an ordinary write.
            block = tuple(s.start for s in oslices)
            if block not in visited:
                changed &= np.asarray(oview, np.float32) != 0
            visited.add(block)
            if changed.any():
                writer = (int(np.ravel_multi_index(coords[:writer_axes],
                                                   grid[:writer_axes]))
                          if writer_axes else 0)
                lw = last_writer[oslices]
                prev = lw[changed]
                clash = (prev >= 0) & (prev != writer)
                if clash.any():
                    others = sorted(set(int(p) for p in prev[clash]))[:4]
                    reg.record(
                        "write-race", kernel_name,
                        f"grid cell {coords} changed "
                        f"{int(clash.sum())} output element(s) of "
                        f"'{out_name}' last written by outer grid "
                        f"row(s) {others} — two grid rows racing on a "
                        f"shared output block (runtime face of SWL902)",
                        {"grid": list(coords), "operand": out_name,
                         "previous_writers": others})
                lw[changed] = writer
    return out_buf, last_writer


# --------------------------------------------------- kernel shadow runs

def ragged_prefill_body(k_pages, row_tables, *, window=None) -> Callable:
    """The in-tree ragged prefill kernel body as its wrapper binds it for
    these pools and tables: what `shadow_ragged_prefill` runs when handed
    no other, and what a drill wraps to seed a crime."""
    from ..ops import attention_pallas as ap

    _, ps, Hkv, D = np.shape(k_pages)
    return functools.partial(
        ap._ragged_prefill_kernel, page_size=ps, n_kv_heads=Hkv,
        pages_per_block=ap._pages_per_block(
            ps, Hkv, D, np.asarray(k_pages).dtype.itemsize,
            np.shape(row_tables)[1]),
        window=window)


def shadow_ragged_prefill(q, sfx_k, sfx_v, k_pages, v_pages, row_tables,
                          starts, lens, prefix_lens, *, window=None,
                          tile: int = 128,
                          kernel: Optional[Callable] = None) -> np.ndarray:
    """Shadow the ragged paged prefill kernel over concrete descriptors:
    bounds-checked refs, write-race diffing, and the canary short-write
    check against the per-row (start, len) descriptors. ``kernel``
    overrides the kernel body (the drill seeds sabotaged variants).
    Returns the shadow output [W, Hq, D]."""
    import jax.numpy as jnp

    from ..ops import attention_pallas as ap

    n_tok = np.asarray(q).shape[0]
    q, sfx_k, sfx_v = (np.asarray(s) for s in ap._pad_stream(
        tile, *(jnp.asarray(s) for s in (q, sfx_k, sfx_v))))
    W, Hq, D = q.shape
    k_pages = np.asarray(k_pages)
    _, ps, Hkv, _ = k_pages.shape
    row_tables = np.asarray(row_tables, np.int32)
    R, maxp = row_tables.shape
    starts = np.asarray(starts, np.int32)
    lens = np.asarray(lens, np.int32)
    plens = np.asarray(prefix_lens, np.int32)
    Tq = min(tile, W)
    n_st = W // Tq
    name = "ragged_paged_prefill_attention"
    ppb = ap._pages_per_block(ps, Hkv, D, k_pages.dtype.itemsize, maxp)
    if kernel is None:
        kernel = ragged_prefill_body(k_pages, row_tables, window=window)

    # the wrapper's grid and index maps, restated (they are closures
    # there): one step a (query block, row); the suffix stream and the
    # pools are whole operands (ANY space) that the kernel copies from
    def q_map(qb, r, table_ref, starts_ref, lens_ref, plens_ref):
        return (qb, 0, 0)

    def whole(arr):
        return (arr, arr.shape, lambda *_: (0,) * arr.ndim)

    out = np.full((W, Hq, D), CANARY, q.dtype)
    G = Hq // Hkv
    # the key buffers start as uninitialised VMEM may: not finite
    halves = np.full((2, max(ppb * ps, Tq), Hkv, D), np.nan, k_pages.dtype)
    out, writers = _run_grid(
        kernel, name, (n_st, R),
        [("table", row_tables), ("starts", starts), ("lens", lens),
         ("plens", plens)],
        [("q", q, (Tq, Hq, D), q_map),
         ("sfx_k", *whole(sfx_k.astype(k_pages.dtype))),
         ("sfx_v", *whole(sfx_v.astype(k_pages.dtype))),
         ("k_pages", *whole(k_pages)),
         ("v_pages", *whole(np.asarray(v_pages)))],
        ("o", out, (Tq, Hq, D), q_map),
        [halves, halves.copy(), np.zeros((2, 2), np.int32),
         np.zeros((Hkv, Tq * G, D), np.float32),
         np.full((Hkv, Tq * G, 128), -1e30, np.float32),
         np.zeros((Hkv, Tq * G, 128), np.float32)],
        writer_axes=2)    # a row step is a writer: rows share a block
    out, writers = out[:n_tok], writers[:n_tok]
    _coverage_rows(name, out, writers, starts, lens)
    return out


def _coverage_rows(kernel: str, out: np.ndarray, writers: np.ndarray,
                   starts: np.ndarray, lens: np.ndarray) -> None:
    """Output-coverage check (the runtime face of SWL905). A
    descriptor-live row fails if EITHER the pre-poisoned canary survives
    in its lanes, OR no grid cell past the exempt init cell ``(0,..,0)``
    ever changed them — the zero-fill idiom wipes the canary at (0, 0),
    so surviving-canary alone cannot see a skipped finalize there."""
    canary = np.asarray(out, np.float32) == CANARY
    for r in range(len(lens)):
        if lens[r] <= 0:
            continue
        s, e = int(starts[r]), int(starts[r]) + int(lens[r])
        region = canary[s:e]
        unwritten = (writers[s:e] < 0).all()
        if region.any() or unwritten:
            why = (f"still carries the canary in "
                   f"{int(region.sum())} element(s)" if region.any()
                   else "was only ever touched by the init cell's "
                        "zero-fill")
            registry().record(
                "short-write", kernel,
                f"row {r} (stream [{s},{e})) {why} — the kernel "
                f"finished the grid without writing output this row's "
                f"descriptor declares live (runtime face of SWL905)",
                {"row": r, "start": s, "len": int(lens[r])})


# -------------------------------------------- descriptor + write shadow

def check_wave_descriptors(tok_row, tok_pos, row_tables, num_pages: int,
                           page_size: int) -> int:
    """Host-side sanity over a ragged wave's WRITE descriptors (the
    ``paged_write_ragged`` operands the engine builds): live tokens must
    target in-range, non-trash pages, and no two live tokens may land on
    the same (page, offset) cell. Returns the number of violations."""
    tok_row = np.asarray(tok_row)
    tok_pos = np.asarray(tok_pos)
    row_tables = np.asarray(row_tables)
    R, maxp = row_tables.shape
    registry().note_check("wave-descriptors")
    before = len(registry().violations())
    live = ((tok_row >= 0) & (tok_row < R)
            & (tok_pos >= 0) & (tok_pos < maxp * page_size))
    if live.any():
        rows = tok_row[live]
        cols = tok_pos[live] // page_size
        pages = row_tables[rows, cols]
        offs = tok_pos[live] % page_size
        oob = (pages < 0) | (pages >= num_pages)
        if oob.any():
            which = np.nonzero(oob)[0][:4]
            registry().record(
                "oob-block", "paged_write_ragged",
                f"live token(s) at stream offset(s) "
                f"{[int(np.nonzero(live)[0][i]) for i in which]} target "
                f"page id(s) {[int(pages[i]) for i in which]} outside "
                f"the pool [0,{num_pages}) — the scatter would write "
                f"out of bounds (runtime face of SWL901)",
                {"pages": [int(pages[i]) for i in which]})
        trash = (pages == 0) & ~oob
        if trash.any():
            which = np.nonzero(trash)[0][:4]
            registry().record(
                "oob-block", "paged_write_ragged",
                f"live token(s) target trash page 0 (stream offset(s) "
                f"{[int(np.nonzero(live)[0][i]) for i in which]}) — a "
                f"row table handed the write path an unallocated page",
                {"rows": [int(rows[i]) for i in which]})
        cell = pages.astype(np.int64) * page_size + offs
        ok = ~oob
        uniq, counts = np.unique(cell[ok], return_counts=True)
        dup = uniq[counts > 1]
        if dup.size:
            registry().record(
                "write-race", "paged_write_ragged",
                f"{int(dup.size)} (page, offset) cell(s) written by "
                f"more than one live token (first: page "
                f"{int(dup[0] // page_size)} offset "
                f"{int(dup[0] % page_size)}) — colliding descriptors "
                f"would leave the pool dependent on scatter order "
                f"(runtime face of SWL902)",
                {"cells": [int(d) for d in dup[:4]]})
    return len(registry().violations()) - before


def shadow_paged_write_ragged(k_pages, v_pages, sfx_k, sfx_v, tok_row,
                              tok_pos, row_tables) -> int:
    """Numpy replay of ``ops.paged_kv.paged_write_ragged`` semantics +
    descriptor checks; parity against the jax result is asserted by the
    checked wrapper. Returns the number of violations recorded."""
    n = check_wave_descriptors(tok_row, tok_pos, row_tables,
                               np.asarray(k_pages).shape[1],
                               np.asarray(k_pages).shape[2])
    return n


# ------------------------------------------------- differential harness

def _random_ragged_case(rng: np.random.Generator):
    """One randomized ragged-prefill scenario: mixed row lengths, page-
    boundary-crossing prefixes, empty rows, and a split row (nonzero
    prefix_len mid-page — the continuation shape a wave split leaves)."""
    import jax.numpy as jnp

    Hkv, G, D, ps, maxp = 2, 2, 8, 4, 3
    Hq = Hkv * G
    R = 4
    P = 2 + R * maxp
    lens = np.zeros(R, np.int32)
    plens = np.zeros(R, np.int32)
    live = rng.permutation(R)[: int(rng.integers(2, R + 1))]
    for r in live:
        lens[r] = int(rng.integers(1, 7))
        # mix: fresh rows, page-aligned prefixes, mid-page splits
        plens[r] = int(rng.choice([0, ps, ps + 1, 2 * ps - 1]))
        plens[r] = min(plens[r], maxp * ps - lens[r])
    starts = np.zeros(R, np.int32)
    acc = 0
    for r in range(R):
        if lens[r]:
            starts[r] = acc
            acc += int(lens[r])
    W = max(8, -(-acc // 8) * 8)
    tables = np.zeros((R, maxp), np.int32)
    free = list(range(1, P))
    rng.shuffle(free)
    for r in range(R):
        need = max(1, -(-int(plens[r] + lens[r]) // ps))
        for c in range(need):
            tables[r, c] = free.pop()
    tok_row = np.full(W, R, np.int32)
    for r in range(R):
        if lens[r]:
            tok_row[starts[r]:starts[r] + lens[r]] = r
    q = jnp.asarray(rng.standard_normal((W, Hq, D)), jnp.float32)
    sfx_k = jnp.asarray(rng.standard_normal((W, Hkv, D)), jnp.float32)
    sfx_v = jnp.asarray(rng.standard_normal((W, Hkv, D)), jnp.float32)
    k_pages = jnp.asarray(rng.standard_normal((P, ps, Hkv, D)),
                          jnp.float32)
    v_pages = jnp.asarray(rng.standard_normal((P, ps, Hkv, D)),
                          jnp.float32)
    return (q, sfx_k, sfx_v, k_pages, v_pages, jnp.asarray(tables),
            jnp.asarray(starts), jnp.asarray(lens), jnp.asarray(plens),
            tok_row)


def differential_ragged_prefill(seed: int = 0, rounds: int = 4,
                                tol: float = _PARITY_TOL,
                                quantized: bool = False) -> int:
    """Randomized kernel-vs-dense-reference parity over ragged
    descriptor soups; a mismatch on any live token is a ``parity``
    violation. Returns the number of mismatching rounds.
    ``quantized=True`` int8-quantizes the random pools and pits the
    quant kernel (in-tile dequant) against the quantized XLA reference
    — the two dequantize identically, so the plain tolerance holds."""
    from ..ops.attention_pallas import (
        ragged_paged_prefill_attention,
        ragged_paged_prefill_attention_quant)
    from ..ops.layers import ragged_prefill_attention_reference
    from ..ops.paged_kv import QuantPool, _quantize_pages

    rng = np.random.default_rng(seed)
    bad = 0
    for i in range(rounds):
        (q, sk, sv, kp, vp, tables, starts, lens, plens,
         tok_row) = _random_ragged_case(rng)
        import jax.numpy as jnp

        if quantized:
            registry().note_check("differential.ragged-prefill.int8")
            kq, ks = _quantize_pages(kp)
            vq, vs = _quantize_pages(vp)
            got = np.asarray(ragged_paged_prefill_attention_quant(
                q, sk, sv, kq, ks, vq, vs, tables, starts, lens, plens,
                interpret=True))
            kp, vp = QuantPool(kq, ks), QuantPool(vq, vs)
        else:
            registry().note_check("differential.ragged-prefill")
            got = np.asarray(ragged_paged_prefill_attention(
                q, sk, sv, kp, vp, tables, starts, lens, plens,
                interpret=True))

        want = np.asarray(ragged_prefill_attention_reference(
            q, sk, sv, kp, vp, tables, starts, lens, plens,
            jnp.asarray(tok_row)))
        live = np.asarray(tok_row) < tables.shape[0]
        err = float(np.max(np.abs(got[live] - want[live]))) \
            if live.any() else 0.0
        if err > tol:
            bad += 1
            registry().record(
                "parity", "ragged_paged_prefill_attention",
                f"differential round {i} (seed {seed}): kernel vs dense "
                f"reference disagree by {err:.3e} (> {tol}) on live "
                f"tokens — descriptor handling diverged",
                {"round": i, "seed": seed, "max_err": err})
    return bad


def _gather_form_chunked(q, k_pages, v_pages, page_table, chunk_k,
                         chunk_v, q_positions, step, window=None):
    """The XLA form of two-segment paged decode attention, what the
    chunked kernels are held to: ``gqa_attention_chunked`` on the
    gathered dense view (a QuantPool dequantizes in the gather), with
    the Pallas dense-slab kernel kept out of it. [B, 1, Hq, D]."""
    from ..ops.layers import gqa_attention_chunked, pallas_disabled
    from ..ops.paged_kv import paged_gather_kv

    kg, vg = paged_gather_kv(k_pages, v_pages, page_table)
    with pallas_disabled():
        return gqa_attention_chunked(q, kg, vg, chunk_k, chunk_v,
                                     q_positions, step, window=window)


def _chunked_kernel_interpreted(q, k_pages, v_pages, page_table, chunk_k,
                                chunk_v, starts, step, live_rows,
                                window=None):
    """The chunked paged decode kernel of this pool's dtype, interpreted,
    and its name. ``q`` [B, Hq, D]; the int8 twin walks every slot and
    takes no list, the plain one walks ``live_rows`` (every slot where
    there is none). [B, Hq, D]."""
    import jax.numpy as jnp

    from ..ops.attention_pallas import (
        paged_decode_gqa_attention_chunked,
        paged_decode_gqa_attention_chunked_quant)
    from ..ops.paged_kv import is_quantized

    starts = jnp.asarray(starts, jnp.int32)
    step = jnp.asarray(step, jnp.int32)
    if is_quantized(k_pages):
        return paged_decode_gqa_attention_chunked_quant(
            q, k_pages.data, k_pages.scale, v_pages.data, v_pages.scale,
            page_table, chunk_k, chunk_v, starts, step, window=window,
            interpret=True), "paged_decode_gqa_attention_chunked_quant"
    if live_rows is None:
        B = q.shape[0]
        live_rows = (jnp.arange(B, dtype=jnp.int32), jnp.int32(B))
    return paged_decode_gqa_attention_chunked(
        q, k_pages, v_pages, page_table, chunk_k, chunk_v, starts, step,
        *live_rows, window=window,
        interpret=True), "paged_decode_gqa_attention_chunked"


def _hold_to_gather_form(kernel: str, got, want, walked: np.ndarray,
                         zeros_elsewhere: bool, tol: float) -> bool:
    """``got`` [B, Hq, D] of a chunked decode kernel against ``want`` of
    the gather form: within ``tol`` on the ``walked`` slots (``parity``)
    and, where the kernel walks a live-row list, exact zeros on every
    other slot (``dead-row``). True when it held."""
    got = np.asarray(got, np.float32)
    slots = np.nonzero(walked)[0]
    ok = _parity(kernel, np.asarray(want), got, slots,
                 np.ones_like(slots), tol=tol)
    dirty = [int(b) for b in np.nonzero(
        (got != 0).any(axis=(1, 2)) & ~walked)[0][:4]]
    if zeros_elsewhere and dirty:
        ok = False
        registry().record(
            "dead-row", kernel,
            f"slot(s) {dirty} are not on the live-row list and do not "
            f"read exact zeros — the kernel wrote a row it was not "
            f"handed (or left its block unfilled)",
            {"slots": dirty})
    return ok


def differential_paged_decode(seed: int = 0, rounds: int = 4,
                              tol: float = _PARITY_TOL,
                              quantized: bool = False) -> int:
    """Randomized parity of the chunked paged decode kernel (the one the
    engine runs, interpreted) against the XLA gather form: mixed prefix
    lengths (none, mid-page, page-aligned), empty slots, the live-row
    list ``live_row_list`` makes of such a table (it leaves the empty
    slots out, and they must read exact zeros), a chunk buffer filled
    up to a random step with garbage behind it. ``quantized=True`` runs
    the int8 twin, which walks every slot, against the quantized gather
    path. Returns the number of mismatching rounds."""
    import jax.numpy as jnp

    from ..ops.paged_kv import QuantPool, _quantize_pages, live_row_list

    rng = np.random.default_rng(seed)
    bad = 0
    for _ in range(rounds):
        B, Hkv, G, D, ps, maxp, Kc = 5, 2, 2, 8, 4, 3, 4
        Hq = Hkv * G
        P = 1 + B * maxp
        step = int(rng.integers(0, Kc))
        # a slot in three holds no sequence (an all-trash table row);
        # slot 0 always does, slot 1 never: every round has both
        held = rng.random(B) < 0.67
        held[0], held[1] = True, False
        starts = np.where(
            held, rng.choice([0, 1, ps - 1, ps, ps + 1, 2 * ps], B),
            0).astype(np.int32)
        table = np.zeros((B, maxp), np.int32)
        free = list(range(1, P))
        rng.shuffle(free)
        for b in np.nonzero(held)[0]:
            for c in range(-(-int(starts[b] + Kc) // ps)):
                table[b, c] = free.pop()
        q = jnp.asarray(rng.standard_normal((B, Hq, D)), jnp.float32)
        kp = jnp.asarray(rng.standard_normal((P, ps, Hkv, D)),
                         jnp.float32)
        vp = jnp.asarray(rng.standard_normal((P, ps, Hkv, D)),
                         jnp.float32)
        ck = jnp.asarray(rng.standard_normal((B, Kc, Hkv, D)),
                         jnp.float32)
        cv = jnp.asarray(rng.standard_normal((B, Kc, Hkv, D)),
                         jnp.float32)
        tbl = jnp.asarray(table)
        if quantized:
            registry().note_check("differential.paged-decode.int8")
            kp = QuantPool(*_quantize_pages(kp))
            vp = QuantPool(*_quantize_pages(vp))
        else:
            registry().note_check("differential.paged-decode")
        got, name = _chunked_kernel_interpreted(
            q, kp, vp, tbl, ck, cv, starts, step, live_row_list(tbl))
        want = _gather_form_chunked(
            q[:, None], kp, vp, tbl, ck, cv,
            jnp.asarray(starts + step)[:, None], jnp.int32(step))[:, 0]
        if not _hold_to_gather_form(
                name, got, want, np.ones(B, bool) if quantized else held,
                not quantized, tol):
            bad += 1
    return bad


# ----------------------------------------------------- checked factories

def _any_tracer(*xs: Any) -> bool:
    import jax

    return any(isinstance(x, jax.core.Tracer) for x in xs)


def checked_ragged_prefill_dispatch(fn: Callable) -> Callable:
    """Wrap ``ops.layers.ragged_prefill_dispatch`` with the shadow
    harness. Flag off: returns ``fn`` itself (type identity)."""
    if not enabled():
        return fn

    @functools.wraps(fn)
    def wrapper(q, sfx_k, sfx_v, k_pages, v_pages, row_tables, starts,
                lens, prefix_lens, tok_row, *, window=None):
        from ..ops.paged_kv import pool_data

        out = fn(q, sfx_k, sfx_v, k_pages, v_pages, row_tables, starts,
                 lens, prefix_lens, tok_row, window=window)
        if (_any_tracer(q, pool_data(k_pages), row_tables)
                or q.shape[0] > _max_shadow_width()):
            return out
        try:
            registry().note_check("shadow.ragged-prefill")
            kp, vp = _dequant_pools(k_pages, v_pages)
            shadow = shadow_ragged_prefill(
                q, sfx_k, sfx_v, kp, vp, row_tables, starts,
                lens, prefix_lens, window=window)
            _parity("ragged_paged_prefill_attention", shadow,
                    np.asarray(out), np.asarray(starts),
                    np.asarray(lens), tol=parity_tol())
        except Exception:
            logger.exception("kerncheck ragged-prefill shadow failed")
        return out

    return wrapper


def checked_paged_attention_dispatch_chunked(fn: Callable) -> Callable:
    """Wrap ``ops.layers.paged_attention_dispatch_chunked``; flag off
    returns ``fn`` itself. On concrete operands the chunked decode
    kernel's result is held to the XLA gather form
    (:func:`_hold_to_gather_form`): the slots of ``live_rows`` within
    :func:`parity_tol`, and exact zeros on every other slot from the
    kernel that walks that list. Where the dispatch gathered (off the
    chip, or under the kv-span threshold) the kernel is run here,
    interpreted, on the same operands."""
    if not enabled():
        return fn

    @functools.wraps(fn)
    def wrapper(q, k_pages, v_pages, page_table, chunk_k, chunk_v,
                q_positions, step, *, window=None, live_rows=None):
        from ..ops.layers import decode_kernel_choice
        from ..ops.paged_kv import is_quantized, pool_data

        out = fn(q, k_pages, v_pages, page_table, chunk_k, chunk_v,
                 q_positions, step, window=window, live_rows=live_rows)
        kd = pool_data(k_pages)
        if (_any_tracer(q, kd, page_table, chunk_k, q_positions, step,
                        *(live_rows or ()))
                or q.shape[0] > _max_shadow_width()):
            return out
        try:
            registry().note_check("dispatch.paged-decode-chunked")
            B = q.shape[0]
            walked = np.ones(B, bool)
            quant = is_quantized(k_pages)
            if live_rows is not None:
                rows, n_live = live_rows
                walked = np.zeros(B, bool)
                walked[np.asarray(rows)[:int(n_live)]] = True
            got, name = out[:, 0], ("paged_decode_gqa_attention_chunked"
                                    + ("_quant" if quant else ""))
            if decode_kernel_choice(
                    page_table.shape[1] * kd.shape[1]) != "pallas":
                got, name = _chunked_kernel_interpreted(
                    q[:, 0], k_pages, v_pages, page_table, chunk_k,
                    chunk_v, q_positions[:, 0] - step, step, live_rows,
                    window=window)
            want = _gather_form_chunked(
                q, k_pages, v_pages, page_table, chunk_k, chunk_v,
                q_positions, step, window=window)
            # the int8 twin takes no list and computes every slot
            _hold_to_gather_form(name, got, want[:, 0], walked,
                                 not quant and live_rows is not None,
                                 parity_tol())
        except Exception:
            logger.exception("kerncheck paged-decode check failed")
        return out

    return wrapper


def checked_ssm_state_read(fn: Callable) -> Callable:
    """Wrap ``models.nemotron_h.ssm_state_read``; flag off returns ``fn``
    itself. On concrete operands the kernel ``ops.ssm_pallas.state_read``
    (the dispatched result where it took the call, else run here,
    interpreted, on a bf16 copy of the pool) is held to the batch-wide
    form ``sum(S C)``: the slots ``rows[:n_live]`` within
    :func:`parity_tol`, exact zeros on every other slot."""
    if not enabled():
        return fn

    @functools.wraps(fn)
    def wrapper(cfg, pool, layer, Cm, rows, n_live):
        import jax.numpy as jnp

        from ..ops import ssm_pallas

        out = fn(cfg, pool, layer, Cm, rows, n_live)
        if (_any_tracer(pool, layer, Cm, rows, n_live)
                or pool.shape[1] > _max_shadow_width()):
            return out
        try:
            registry().note_check("dispatch.ssm-state-read")
            B, H = pool.shape[1], cfg.ssm_heads
            S = pool.astype(jnp.bfloat16)
            got = out
            if not ssm_pallas.takes(pool, cfg.ssm_groups):
                got = ssm_pallas.state_read(S, layer, Cm, rows, n_live,
                                            interpret=True)
            per = H // cfg.ssm_groups
            want = jnp.sum(S[layer].astype(jnp.float32).reshape(
                B, H, -1, S.shape[-1]) * jnp.repeat(Cm, per, axis=1)[
                    :, :, None, :], axis=-1)
            walked = np.zeros(B, bool)
            walked[np.asarray(rows)[:int(n_live)]] = True
            _hold_to_gather_form("ssm_state_read",
                                 np.asarray(got).reshape(want.shape), want,
                                 walked, True, parity_tol("bfloat16"))
        except Exception:
            logger.exception("kerncheck ssm-state-read check failed")
        return out

    return wrapper


def checked_paged_write_ragged(fn: Callable) -> Callable:
    """Wrap ``ops.paged_kv.paged_write_ragged`` with descriptor checks
    + numpy scatter replay parity; flag off returns ``fn`` itself."""
    if not enabled():
        return fn

    @functools.wraps(fn)
    def wrapper(k_pages, v_pages, sfx_k, sfx_v, tok_row, tok_pos,
                row_tables):
        from ..ops.paged_kv import is_quantized, pool_data

        out = fn(k_pages, v_pages, sfx_k, sfx_v, tok_row, tok_pos,
                 row_tables)
        if _any_tracer(pool_data(k_pages), sfx_k, tok_row, row_tables):
            return out
        try:
            registry().note_check("shadow.paged-write-ragged")
            n = check_wave_descriptors(
                tok_row, tok_pos, row_tables,
                pool_data(k_pages).shape[1],
                pool_data(k_pages).shape[2])
            if n == 0:
                if is_quantized(k_pages):
                    _replay_write_parity_quant(sfx_k, tok_row, tok_pos,
                                               row_tables, out[0])
                else:
                    _replay_write_parity(k_pages, sfx_k, tok_row,
                                         tok_pos, row_tables, out[0])
        except Exception:
            logger.exception("kerncheck paged-write shadow failed")
        return out

    return wrapper


def _replay_write_parity(k_pages, sfx_k, tok_row, tok_pos, row_tables,
                         out_k) -> None:
    """Replay the ragged scatter in numpy (in stream order; collision-
    free per the descriptor check) and compare the K result."""
    kp = np.array(np.asarray(k_pages), copy=True)
    sk = np.asarray(sfx_k)
    tok_row = np.asarray(tok_row)
    tok_pos = np.asarray(tok_pos)
    tables = np.asarray(row_tables)
    R, maxp = tables.shape
    ps = kp.shape[2]
    for t in range(tok_row.shape[0]):
        r = int(np.clip(tok_row[t], 0, R - 1))
        col = int(np.clip(tok_pos[t] // ps, 0, maxp - 1))
        page = int(tables[r, col])
        dead = (tok_pos[t] >= maxp * ps or tok_row[t] < 0
                or tok_row[t] >= R)
        if dead:
            page, off = 0, 0
        else:
            off = int(tok_pos[t] % ps)
        kp[:, page, off] = sk[:, t].astype(kp.dtype)
    got = np.asarray(out_k)
    if not np.array_equal(
            np.asarray(got, np.float32), np.asarray(kp, np.float32)):
        ndiff = int(np.sum(np.asarray(got, np.float32)
                           != np.asarray(kp, np.float32)))
        registry().record(
            "parity", "paged_write_ragged",
            f"scatter result differs from the per-token replay in "
            f"{ndiff} element(s) — positional write math diverged",
            {"ndiff": ndiff})


def _replay_write_parity_quant(sfx_k, tok_row, tok_pos, row_tables,
                               out_k) -> None:
    """Positional check for the QUANTIZED ragged write: dequantize each
    live token's landing slot from the written pool and compare to the
    suffix value. The window requant is not bit-replayed — instead the
    round-to-nearest bound (half a scale step per element) pins the
    slot: a token scattered to the wrong (page, offset) misses its
    value by far more than scale/2."""
    tok_row = np.asarray(tok_row)
    tok_pos = np.asarray(tok_pos)
    tables = np.asarray(row_tables)
    R, maxp = tables.shape
    data = np.asarray(out_k.data)           # [L, P, ps, Hkv, D] int8
    scale = np.asarray(out_k.scale, np.float32)  # [L, P, Hkv]
    ps = data.shape[2]
    sk = np.asarray(sfx_k, np.float32)      # [L, W, Hkv, D]
    worst = 0.0
    for t in range(tok_row.shape[0]):
        if not (0 <= tok_row[t] < R and 0 <= tok_pos[t] < maxp * ps):
            continue                        # dead token -> trash page
        page = int(tables[int(tok_row[t]), int(tok_pos[t]) // ps])
        off = int(tok_pos[t]) % ps
        s = scale[:, page]                  # [L, Hkv]
        deq = data[:, page, off].astype(np.float32) * s[..., None]
        err = np.abs(deq - sk[:, t])
        # per-(layer, head) budget: half a quant step + fp slack
        over = err - (0.5 * s[..., None] + 1e-6)
        worst = max(worst, float(np.max(over)))
    if worst > 0.0:
        registry().record(
            "parity", "paged_write_ragged",
            f"quantized scatter: a live token's dequantized slot "
            f"misses its suffix value by {worst:.3e} beyond the "
            f"half-step rounding budget — positional write math or "
            f"scale bookkeeping diverged",
            {"max_over": worst})


def _parity(kernel: str, shadow: np.ndarray, dispatched: np.ndarray,
            starts: np.ndarray, lens: np.ndarray,
            tol: float = _PARITY_TOL) -> bool:
    """Compare shadow (or reference) vs dispatched output on
    descriptor-live rows; a NaN is a mismatch. True when they agree."""
    worst = 0.0
    for r in range(len(lens)):
        if lens[r] <= 0:
            continue
        s, e = int(starts[r]), int(starts[r]) + int(lens[r])
        a = np.asarray(shadow[s:e], np.float32)
        b = np.asarray(dispatched[s:e], np.float32)
        worst = float(np.maximum(worst, np.max(np.abs(a - b))))
    if worst <= tol:
        return True
    registry().record(
        "parity", kernel,
        f"shadow interpreter (or reference form) vs dispatched output "
        f"disagree by {worst:.3e} (> {tol}) on live rows — the "
        f"dispatched path and the kernel math diverged",
        {"max_err": worst})
    return False
