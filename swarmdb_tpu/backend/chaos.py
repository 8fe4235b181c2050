"""Deterministic fault injection for the SERVING path (ISSUE 9).

``ha/chaos.py`` drives the control plane (kill/partition/delay of HA
nodes); this module drives the ENGINE layer through the seam points the
supervisor watches, so a chaos scenario reads as a script and every
injected fault lands in the flight recorder's event ring:

    chaos = ServingChaos(group)
    chaos.kill_lane(1)          # decode thread dies (LaneKilled escapes
                                # the loop's recovery handler)
    chaos.wedge(0)              # dispatch hangs: beats starve, thread
                                # stays alive — the SUSPECT signature
    chaos.slow(2, 0.05)         # per-step latency injection
    chaos.squeeze_pool(0.9)     # withdraw 90% of free pages: watermark
                                # backpressure + shedding territory
    chaos.heal(0)               # clear wedge/slow on one lane
    chaos.heal_pool()           # return every squeezed page

Faults are applied at exactly two seams, both owned by the engine:

- ``Engine.chaos_step`` — called once per decode-loop iteration on the
  engine thread, before admission. Kill raises :class:`LaneKilled` (a
  ``BaseException``, so the loop's ``except Exception`` recovery cannot
  swallow it and the thread dies for real — the crash the supervisor
  exists for). Wedge blocks here; slow sleeps here. The resident-session
  continue vote polls ``pending()`` so an armed fault lands at the seam
  within one chunk even mid-session (one more when it is armed from a
  request's own ``on_token``: the vote on the block being emitted has
  been taken).
- ``PageAllocator.reserve`` — pool squeeze withdraws free pages from
  circulation, indistinguishable from a burst of long-lived occupants.

``wait_until`` is re-exported from ``ha.chaos``: a chaos test's only
sleeping is a bounded convergence poll against the thresholds under
test.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..obs.flight import FlightRecorder
from ..ha.chaos import wait_until
from .engine import Engine
from ..utils.sync import make_lock

__all__ = ["LaneKilled", "ServingChaos", "wait_until"]


class LaneKilled(BaseException):
    """Injected lane death. Deliberately a BaseException: the engine
    loop's in-place error recovery catches ``Exception``, and a chaos
    KILL must produce a genuinely dead thread (the failure mode lane
    supervision exists to detect), not a self-healed one."""


class _LaneFault:
    """Per-lane fault state, installed as ``Engine.chaos_step``."""

    def __init__(self, on_fire) -> None:
        self._on_fire = on_fire
        self._kill = threading.Event()
        self._wedge = threading.Event()
        self._delay = 0.0

    def pending(self) -> bool:
        """True when a fault is armed that must land at the loop-top
        seam (polled by the resident session's continue vote)."""
        return self._kill.is_set() or self._wedge.is_set()

    def __call__(self, eng: Engine) -> None:
        if self._kill.is_set():
            self._kill.clear()  # one-shot: the restarted lane runs clean
            self._on_fire("kill_fired")
            raise LaneKilled("chaos: lane killed")
        if self._wedge.is_set():
            self._on_fire("wedge_holding")
            while self._wedge.is_set():
                # the engine thread is pinned HERE: beats starve while
                # the thread stays alive — exactly a hung device dispatch
                time.sleep(0.01)
        if self._delay > 0:
            time.sleep(self._delay)


class ServingChaos:
    """Scripted fault injection over a lane group (or one engine)."""

    def __init__(self, engine_or_group: Any,
                 flight: Optional[FlightRecorder] = None) -> None:
        self.lanes: List[Engine] = list(
            getattr(engine_or_group, "lanes", None) or [engine_or_group])
        self.flight = flight if flight is not None else getattr(
            engine_or_group, "flight", None) or FlightRecorder()
        self.events: List[Dict[str, Any]] = []
        self._events_lock = make_lock("backend.chaos.ServingChaos._events_lock")
        self._timers: List[threading.Timer] = []
        self._t0 = time.monotonic()
        self._reserved: Dict[int, List[int]] = {}
        self.faults: List[_LaneFault] = []
        for idx, eng in enumerate(self.lanes):
            fault = _LaneFault(
                lambda what, i=idx: self._log(what, i, fired=True))
            self.faults.append(fault)
            eng.chaos_step = fault

    def _log(self, action: str, lane: int, **detail: Any) -> None:
        if detail.get("fired") and action == "wedge_holding":
            return  # the hold loop would spam one event per 10ms tick
        ev = {"t_mono": round(time.monotonic() - self._t0, 4),
              "action": action, "lane": lane, **detail}
        with self._events_lock:
            self.events.append(ev)
        self.flight.record_event(
            {"kind": f"chaos.{action}", "lane": lane,
             **{k: v for k, v in detail.items() if k != "fired"}})

    # --------------------------------------------------------------- faults

    def kill_lane(self, lane: int) -> None:
        """Arm a one-shot decode-thread death on the lane's next loop
        iteration (mid-session kills land within one chunk via the
        continue-vote poll)."""
        self._log("kill_lane", lane)
        self.faults[lane]._kill.set()

    def wedge(self, lane: int) -> None:
        """Pin the lane's engine thread at the dispatch seam until
        heal(): live thread, starved beats."""
        self._log("wedge", lane)
        self.faults[lane]._wedge.set()

    def slow(self, lane: int, seconds: float) -> None:
        """Inject per-step latency (a degraded, not dead, lane)."""
        self._log("slow", lane, seconds=seconds)
        self.faults[lane]._delay = float(seconds)

    def heal(self, lane: int) -> None:
        """Clear wedge/slow on one lane (kills are one-shot and the
        supervisor owns the restart)."""
        self._log("heal", lane)
        self.faults[lane]._wedge.clear()
        self.faults[lane]._delay = 0.0

    def squeeze_pool(self, fraction: float = 1.0,
                     lane: Optional[int] = None,
                     drain_cache: bool = True) -> int:
        """Withdraw ``fraction`` of each (paged) lane's reclaimable
        pages from circulation. ``drain_cache`` first evicts the
        UNPINNED prefix-cache pages into the free list and squeezes
        those too — a warm cache is legitimate headroom (admission
        evicts it on demand), so a free-list-only squeeze on a warm
        engine creates no real pressure. Returns the total withdrawn."""
        taken = 0
        targets = [lane] if lane is not None else range(len(self.lanes))
        for i in targets:
            eng = self.lanes[i]
            if eng.paged is None:
                continue
            alloc = eng.paged.allocator
            if drain_cache and eng._prefix is not None:
                evicted = eng._prefix.evict_lru(eng.paged.num_pages)
                if evicted:
                    alloc.add_free(evicted)
            n = max(0, int(fraction * alloc.free_count()))
            pages = alloc.reserve(n)
            self._reserved.setdefault(i, []).extend(pages)
            taken += len(pages)
            self._log("squeeze_pool", i, pages=len(pages),
                      fraction=fraction)
        return taken

    def heal_pool(self, lane: Optional[int] = None) -> None:
        """Return every squeezed page to its lane's free list."""
        targets = [lane] if lane is not None else list(self._reserved)
        for i in targets:
            pages = self._reserved.pop(i, [])
            if pages and self.lanes[i].paged is not None:
                self.lanes[i].paged.allocator.add_free(pages)
                self._log("heal_pool", i, pages=len(pages))

    # ------------------------------------------------------------ scheduling

    def schedule(self, at_s: float, action: str, *args: Any
                 ) -> threading.Timer:
        """Fire ``action`` (kill_lane/wedge/slow/heal/squeeze_pool/
        heal_pool) ``at_s`` seconds from now (same scheduling shape as
        ha/chaos.py: single-threaded fault application + the event log
        carry the determinism)."""
        fn = getattr(self, action)
        t = threading.Timer(at_s, fn, args=args)
        t.daemon = True
        t.start()
        self._timers.append(t)
        return t

    def run_script(self, script: Sequence[Tuple[float, str, tuple]]) -> None:
        """[(at_s, action, args), ...] — a whole scenario at once."""
        for at_s, action, args in script:
            self.schedule(at_s, action, *args)

    # -------------------------------------------------------------- teardown

    def stop(self) -> None:
        """Cancel pending faults, heal everything, uninstall the seams."""
        for t in self._timers:
            t.cancel()
        self.heal_pool()
        for i, (eng, fault) in enumerate(zip(self.lanes, self.faults)):
            fault._kill.clear()
            fault._wedge.clear()
            fault._delay = 0.0
            eng.chaos_step = None

    def dump(self) -> Dict[str, Any]:
        with self._events_lock:
            events = list(self.events)
        return {"chaos_events": events,
                "flight": self.flight.dump("serving_chaos")}
