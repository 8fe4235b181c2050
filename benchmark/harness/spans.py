"""The program's own spans, for the per-layer metrics that read them.

The program keeps closed spans in per-thread rings
(``swarmdb_tpu/obs/tracer.py``); the benchmark runs in the same process, so
a reader takes them from ``TRACER`` after the run. A ring holds the newest
events only: where one that holds spans of the wanted category was lapped
since the window began (``written - capacity`` events lost, and the oldest
it still holds ended inside the window or later), part of the window is
gone, and the reader gets ``None`` with a note and never a number from a
partial ring. A program without these spans (an older commit) gives an
empty list, and its reader returns ``None``.

A test hands its own ``ctx["spans"]`` (dicts as ``TRACER.snapshot()``
returns them) and ``ctx["ring_stats"]`` instead."""

from __future__ import annotations

from typing import Any, Dict, List, Optional


def engine_spans(ctx: Dict[str, Any], metric: str,
                 cat: str = "engine") -> Optional[List[Dict[str, Any]]]:
    """Every held span of ``cat``, oldest first, each with ``end_s`` and
    its ``args`` a dict; ``None`` (and a note under ``metric``) when a ring
    that holds such spans lost events of the window."""
    spans, rings = ctx.get("spans"), ctx.get("ring_stats")
    if spans is None:
        from swarmdb_tpu.obs import TRACER

        spans = TRACER.snapshot()
        stats = getattr(TRACER, "ring_stats", None)
        rings = stats() if stats is not None else []
    out = []
    for e in spans:
        if e["cat"] == cat:
            out.append(dict(e, end_s=e["start_s"] + e["dur_us"] * 1e-6,
                            args=e["args"] or {}))
    holders = {e["tid"] for e in out}
    for ring in rings or []:
        if (ring["tid"] in holders and ring["lost"] > 0
                and ring["oldest_end_s"] is not None
                and ring["oldest_end_s"] >= ctx["t0"]):
            ctx["notes"][metric] = {
                "unread": f"ring of thread {ring['thread']} lapped inside "
                          f"the window: {ring['lost']} events lost of "
                          f"{ring['written']} (SWARMDB_TRACE_RING "
                          f"{ring['capacity']})"}
            return None
    return out


def in_window(ctx: Dict[str, Any], t: float) -> bool:
    return ctx["t0"] <= t < ctx["t0"] + ctx["seconds"]
