"""From a profiler trace to numbers: device busy and idle, time per
program and per operation, the longest idle gaps and what the host was
doing in them. Works on plain events so that it can be checked on a small
recorded trace: ``load_xplane`` turns an ``.xplane.pb`` into

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

and ``reduce`` does the rest. On a TPU each chip is a plane
``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per executed
operation and whose line ``XLA Modules`` holds one per executed program
(``jit_<function>(<fingerprint>)``)."""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
ATTRIBUTED_GAPS = 200


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_xplane(path: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [e.name, float(e.start_ns), float(e.duration_ns)]
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted, disjoint cover of a set of [start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(cover: List[Tuple[float, float]], s: float, e: float) -> float:
    """Length of ``cover`` (sorted, disjoint) inside [s, e)."""
    return sum(max(0.0, min(e, b) - max(s, a)) for a, b in cover
               if b > s and a < e)


def program_name(module_event: str) -> str:
    """``jit__decode_resident(1234)`` -> ``_decode_resident``."""
    name = re.sub(r"\(.*\)$", "", module_event).strip()
    return name[4:] if name.startswith("jit_") else name


def _line(plane, name):
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def short_name(op_event: str) -> str:
    """An operation's event is named by its whole HLO text
    (``%fusion.3 = bf16[16,4096]{...} fusion(...)``): keep ``fusion.3``."""
    return op_event.split(" = ", 1)[0].lstrip("%")


def kernel_name(op_event: str) -> str:
    """``%paged_decode_gqa_attention_chunked.7 = ...`` ->
    ``paged_decode_gqa_attention_chunked``: a Pallas kernel's custom call
    is named after the kernel's function in the program's source."""
    return re.sub(r"\.\d+$", "", short_name(op_event))


def nest(events: List[List[Any]]):
    """Operations nest: a ``while`` event spans those of its body. Returns
    (self seconds by short name in ns, the leaf intervals). Self time is
    an event's duration less its direct children's; a leaf has none."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    child = [0.0] * len(events)
    parent_of_any = [False] * len(events)
    stack: List[int] = []
    for i in order:
        _, s, d = events[i]
        # a parent contains its child whole
        while stack and events[stack[-1]][1] + events[stack[-1]][2] < s + d:
            stack.pop()
        if stack:
            child[stack[-1]] += d
            parent_of_any[stack[-1]] = True
        stack.append(i)
    self_ns: Dict[str, float] = {}
    leaves = []
    for i, (n, s, d) in enumerate(events):
        key = short_name(n)
        self_ns[key] = self_ns.get(key, 0.0) + max(0.0, d - child[i])
        if not parent_of_any[i]:
            leaves.append((s, s + d))
    return self_ns, leaves


def reduce(trace: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    """Busy and idle seconds, per-program, per-operation and per-kernel
    device seconds, and the idle gaps named by what the host was doing.

    ``window_s`` spans from the first to the last event of any plane.
    ``busy_s`` is the union of the leaf operations' intervals (a ``while``
    that waits for the host between two chunks is not busy while it
    waits), averaged over the device planes. ``programs[name]`` holds
    ``busy_s`` (leaf-operation time inside that program's module events),
    ``span_s`` (the module events themselves) and ``calls``.
    ``op_seconds`` is self time by operation; ``kernels[name]`` is the
    time and count of the events of one kernel or fusion family."""
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError("no /device:TPU:<n> plane in the trace: planes are "
                         + ", ".join(p["name"] for p in trace["planes"]))
    starts, ends = [], []
    for p in trace["planes"]:
        for line in p["lines"]:
            for _, s, d in line["events"]:
                starts.append(s)
                ends.append(s + d)
    t_lo, t_hi = min(starts), max(ends)
    host: List[Tuple[str, float, float]] = []
    for p in trace["planes"]:
        if p["name"].startswith("/host:CPU"):
            for line in p["lines"]:
                host.extend((n, s, s + d) for n, s, d in line["events"]
                            if d > 0)

    busy_total = 0.0
    ops: Dict[str, float] = {}
    kernels: Dict[str, Dict[str, float]] = {}
    programs: Dict[str, Dict[str, float]] = {}
    gaps: Dict[str, float] = {}
    for plane in devices:
        op_events = _line(plane, OPS_LINE)
        self_ns, leaves = nest(op_events)
        cover = union(leaves)
        busy_total += sum(e - s for s, e in cover)
        for n, v in self_ns.items():
            ops[n] = ops.get(n, 0.0) + v
        for n, _, d in op_events:
            row = kernels.setdefault(kernel_name(n), {"seconds": 0.0,
                                                      "calls": 0})
            row["seconds"] += d * 1e-9
            row["calls"] += 1
        for n, s, d in _line(plane, MODULES_LINE):
            row = programs.setdefault(program_name(n), {
                "busy_s": 0.0, "span_s": 0.0, "calls": 0})
            row["busy_s"] += overlap(cover, s, s + d) * 1e-9
            row["span_s"] += d * 1e-9
            row["calls"] += 1
        edges = [t_lo] + [x for s, e in cover for x in (s, e)] + [t_hi]
        idle = sorted(((b - a, a, b) for a, b in zip(edges[0::2], edges[1::2])
                       if b > a), reverse=True)
        # name the longest gaps by the host event that covers most of
        # each; the many short ones between operations stay one entry
        for length, a, b in idle[:ATTRIBUTED_GAPS]:
            best, best_len = "host: nothing traced", 0.0
            for n, s, e in host:
                ov = min(b, e) - max(a, s)
                if ov > best_len:
                    best, best_len = n, ov
            gaps[best] = gaps.get(best, 0.0) + length
        rest = sum(g[0] for g in idle[ATTRIBUTED_GAPS:])
        if rest:
            gaps["short gaps between operations"] = (
                gaps.get("short gaps between operations", 0.0) + rest)
    n_dev = len(devices)

    def top_of(d):
        return [[k, v * 1e-9 / n_dev] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"window_s": (t_hi - t_lo) * 1e-9,
            "busy_s": busy_total * 1e-9 / n_dev,
            "devices": n_dev, "programs": programs, "kernels": kernels,
            "op_seconds": {k: v * 1e-9 / n_dev for k, v in ops.items()},
            "breakdown": {"device_ops": top_of(ops),
                          "idle_gaps": top_of(gaps)}}


def summary(trace: Dict[str, Any], per_line: int = 40) -> Dict[str, Any]:
    """What a trace holds, for reading one by hand: planes, lines, and each
    line's most time-consuming event names."""
    out = {}
    for p in trace["planes"]:
        lines = {}
        for line in p["lines"]:
            agg: Dict[str, List[float]] = {}
            for n, _, d in line["events"]:
                row = agg.setdefault(n, [0, 0.0])
                row[0] += 1
                row[1] += d
            lines[line["name"]] = {
                "events": len(line["events"]),
                "top": [[n, c, round(t * 1e-9, 6)] for n, (c, t) in sorted(
                    agg.items(), key=lambda kv: -kv[1][1])[:per_line]]}
        out[p["name"]] = lines
    return out


def peek(path: str, per_line: int = 400) -> Dict[str, Any]:
    """The first events of every device line with their stats, as they are
    in the file: for reading a trace by hand, and for cutting a fixture."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {}
        for line in plane.lines:
            rows = []
            for i, e in enumerate(line.events):
                if i >= per_line:
                    break
                row = [e.name, float(e.start_ns), float(e.duration_ns)]
                if i < 40:
                    row.append({str(k): str(v)[:200] for k, v in e.stats})
                rows.append(row)
            lines[line.name] = rows
        out[plane.name] = lines
    return out
