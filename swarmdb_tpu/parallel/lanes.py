"""Per-shard admission lanes: DP serving as N independent single-device
engines behind one Engine-shaped facade (ISSUE 8 tentpole, part a).

Why lanes instead of one GSPMD engine: a DP-sharded engine runs ONE
program per step over the whole mesh — so every admission wave's prefill
lands on EVERY shard's stream, and all eight shards' decode chunks queue
behind one shard's admission. The PR 5/6 analyzer put numbers on it
(checked-in dpserve traces): dp8 paid 6.2x per-completion cost, 83% of
the growth in queue wait — admission serialization — while the shards
were evenly loaded. Splitting the mesh into per-device engines makes the
serialization structurally impossible:

- Each lane is a complete single-device paged engine (own params copy —
  exactly what DP replication means — own page pool, own prefix cache,
  own admission queue, own decode loop thread, own device stream).
- Admission is PER LANE: lane d popping its queue and dispatching its
  prefill touches only device d; the other lanes' device-resident decode
  sessions (engine.py emission ring) never wait on it. The
  ``engine_admission_overlap_steps`` counter records exactly these
  overlapped waves.
- Routing preserves the conversation/prefix affinity the sharded
  allocator enforced structurally: a request's ``shard_hint`` (the
  serving layer's conversation-stable hash) pins it to one lane, so its
  prefix-cache pages stay hittable across turns; unhinted requests go to
  the least-loaded lane.
- Priorities and anti-starvation aging work per lane unchanged
  (``Engine._age_queue``); hint routing keeps each conversation's turns
  in ONE lane's queue, so a lane-local age bump has the same effect the
  global queue's did.

The facade exposes the Engine surface ``ServingService``/bench/dashboard
actually consume (submit/cancel/stats/warmup/flight/paged/prefix), so
the serving stack drops in unchanged. ``SWARMDB_ADMIT_OVERLAP=0``
restores the single-program GSPMD engine
(``parallel/serving.build_sharded_paged``).
"""

from __future__ import annotations

import concurrent.futures
import logging
import threading
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from ..backend.engine import Engine, GenRequest
from ..obs import TRACER, FlightRecorder
from ..utils.metrics import MetricsRegistry
from ..utils.sync import make_lock

logger = logging.getLogger("swarmdb_tpu.lanes")

__all__ = ["ShardLaneGroup", "LaneGroupInfo", "build_lane_group"]


@dataclass
class LaneGroupInfo:
    """What ``build_serving_engine`` callers get in the ShardedModel slot
    when the lane group engages: enough identity to keep the call sites
    (api/server.py reads ``.cfg``) working."""

    cfg: Any
    mesh: Any
    data_size: int


class _LaneAllocatorView:
    """Aggregate allocator facade: ``n_shards`` routes the serving
    layer's shard hints (and disables rolling resume, which needs
    single-pool page custody), ``stats()`` feeds the bench record."""

    def __init__(self, group: "ShardLaneGroup") -> None:
        self._group = group

    @property
    def n_shards(self) -> int:
        return len(self._group.lanes)

    def stats(self) -> Dict[str, Any]:
        per = [e.paged.allocator.stats() for e in self._group.lanes]
        return {
            "num_pages": sum(s["num_pages"] for s in per),
            "page_size": per[0]["page_size"],
            "free_pages": sum(s.get("free_pages", 0) for s in per),
            "lanes": len(per),
            "pages_allocated_total": sum(
                s.get("pages_allocated_total", 0) for s in per),
            "pages_freed_total": sum(
                s.get("pages_freed_total", 0) for s in per),
            # per-lane churn for the /metrics counters (ISSUE 13)
            "churn_by_lane": [
                (s.get("pages_allocated_total", 0),
                 s.get("pages_freed_total", 0)) for s in per],
        }


class _LanePagedView:
    """Engine.paged stand-in (truthy, allocator + page_size)."""

    def __init__(self, group: "ShardLaneGroup") -> None:
        self.allocator = _LaneAllocatorView(group)
        self.page_size = group.lanes[0].paged.page_size
        self.num_pages = sum(e.paged.num_pages for e in group.lanes)


class _LanePrefixView:
    """Engine._prefix stand-in: the bench's hit-rate accounting sums the
    per-lane caches (same-lane-only reuse, like the sharded pool's
    same-shard-only rule)."""

    def __init__(self, group: "ShardLaneGroup") -> None:
        self._group = group

    def stats(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for e in self._group.lanes:
            if e._prefix is None:
                continue
            for k, v in e._prefix.stats().items():
                if isinstance(v, (int, float)):
                    out[k] = out.get(k, 0) + v
        return out


class ShardLaneGroup:
    """N single-device engines behind the Engine facade."""

    def __init__(self, lanes: List[Engine], info: LaneGroupInfo,
                 flight_dir: Optional[str] = None) -> None:
        assert lanes, "a lane group needs at least one engine"
        self.lanes = lanes
        self.info = info
        ref = lanes[0]
        self.max_batch = sum(e.max_batch for e in lanes)
        self.max_seq = ref.max_seq
        self.decode_chunk = ref.decode_chunk
        self.prefill_batch = ref.prefill_batch
        self.metrics = ref.metrics
        self.params = ref.params          # bench MFU/device identity
        self.tracer = TRACER
        self._mh = None                   # lanes never run pod mode
        self._flight_dir = flight_dir if flight_dir is not None \
            else ref._flight_dir
        # ONE flight recorder for the whole group: step records carry
        # their lane in "shard", request timelines interleave. Multiple
        # lane threads write the rings concurrently — a benign race that
        # can at worst drop one diagnostic record (the rings are
        # evidence, not accounting; counters stay exact).
        self.flight = FlightRecorder()
        self.flight.meta.update({
            "mesh": {k: int(v) for k, v in info.mesh.shape.items()}
            if info.mesh is not None else {},
            "paged_shards": len(lanes),
            "admit_overlap": True,
            # per-lane waves run the packed ragged prefill (ISSUE 11):
            # each lane's admission wave is ONE no-padding token stream
            # whose width comes off the power-of-two ladder, dispatched
            # on that lane's device stream — the packing is lane-local,
            # so it composes with (not fights) the admission overlap
            "ragged_prefill": bool(
                getattr(ref, "_prefill_ragged_fused", None) is not None),
            "max_batch": self.max_batch,
            "max_seq": self.max_seq,
        })
        self.paged = _LanePagedView(self)
        self._prefix = (_LanePrefixView(self)
                        if any(e._prefix is not None for e in lanes)
                        else None)
        self._prefix_ps = getattr(ref, "_prefix_ps", None)
        self._sentinel = None
        # lane supervisor (backend/supervisor.py, ISSUE 9): attached by
        # the serving layer (or tests). When present, submissions are
        # adopted (deadline/retry budgets, migration tracking) and
        # routing excludes quarantined lanes.
        self.supervisor = None
        # tier-aware routing hook (ISSUE 19): GenRequest -> lane index
        # whose warm store holds the request's conversation, or None.
        # A warm-resident lane beats the least-loaded cold lane — the
        # promotion stays a host->device copy instead of a full
        # re-prefill on a lane that never saw the conversation.
        self.tier_locator: Optional[Callable[[GenRequest], Optional[int]]] = None
        # swarmfleet (ISSUE 20): SWARMDB_FLEET_TIERS per-lane speed/
        # reliability weights. DeServe-style: a slow tier is weighted
        # DOWN in the load score, not excluded — and CRITICAL traffic
        # pins to the fastest admissible lanes. None = homogeneous.
        self.lane_weights: Optional[List[float]] = None
        self.fleet = None
        self._rr = 0
        self._rr_lock = make_lock("parallel.lanes.ShardLaneGroup._rr_lock")
        for idx, eng in enumerate(lanes):
            eng.flight = self.flight
            eng.flight_shard = idx
            eng._flight_dir = self._flight_dir
            eng.overlap_probe = self._make_probe(idx)
            # swarmprof duty cycles name lanes the way pagecheck does:
            # lane d's busy fraction is the admission-overlap win made
            # into a per-lane number (GET /admin/profile, /metrics)
            eng._prof.set_label(f"lane{idx}")
            # swarmmem pool residency carries the same lane naming, so
            # the /admin/mem occupancy rows line up with duty cycles
            if eng.paged is not None:
                eng.paged.allocator.mem.set_label(f"lane{idx}")
        # swarmfleet (ISSUE 20): SWARMDB_FLEET=prefill:N,decode:M
        # partitions the lanes into role-typed pools. Built HERE — before
        # warmup() — so role-restricted warmup plans shrink each lane's
        # compile count (prefill lanes skip resident-decode variants and
        # vice versa). Default off: colocated, bit-for-bit untouched.
        from .fleet import build_fleet, parse_tier_weights

        self.lane_weights = parse_tier_weights(len(lanes))
        self.fleet = build_fleet(self)

    def _make_probe(self, idx: int) -> Callable[[], bool]:
        def probe() -> bool:
            return any(e._lane_busy for j, e in enumerate(self.lanes)
                       if j != idx)
        return probe

    # --------------------------------------------------------- lifecycle

    def start(self) -> None:
        for e in self.lanes:
            e.start()

    def stop(self) -> None:
        for e in self.lanes:
            e.stop()

    def alive(self) -> bool:
        """Without a supervisor, any dead lane makes the group "dead"
        (the serving watchdog then restarts the dead ones via
        restart()). WITH a supervisor, single-lane death is the
        supervisor's job — quarantine, migrate, restart, probe, re-admit
        — so the group only reads dead when EVERY lane is gone."""
        if self.supervisor is not None:
            return any(e.alive() for e in self.lanes)
        return all(e.alive() for e in self.lanes)

    def restart(self) -> None:
        """Restart only the DEAD lanes: a single lane's decode-loop death
        must not fail the seven healthy lanes' in-flight requests."""
        for e in self.lanes:
            if not e.alive():
                e.restart()

    def warmup(self) -> float:
        """Warm every lane CONCURRENTLY: compilation releases the GIL
        (XLA C++), and with the persistent cache on, the first lane to
        compile a variant serializes it for the rest — so group warmup
        costs ~one lane's warmup, not N."""
        import time

        t0 = time.time()
        if len(self.lanes) == 1:
            self.lanes[0].warmup()
        else:
            with concurrent.futures.ThreadPoolExecutor(
                    max_workers=min(8, len(self.lanes))) as ex:
                list(ex.map(lambda e: e.warmup(), self.lanes))
        return time.time() - t0

    # -------------------------------------------------------- scheduling

    def _admissible(self) -> List[int]:
        """Lane indices currently taking admissions. A quarantined lane
        (supervisor verdict) is excluded; if EVERY lane is quarantined
        the full set is returned — queueing on a recovering lane beats
        refusing outright (deadlines bound the wait)."""
        sup = self.supervisor
        if sup is None:
            return list(range(len(self.lanes)))
        ok = [j for j in range(len(self.lanes)) if sup.lane_admissible(j)]
        return ok or list(range(len(self.lanes)))

    def _route(self, request: GenRequest,
               within: Optional[List[int]] = None) -> "Tuple[int, Engine]":
        ok = self._admissible()
        if within:
            # pool-restricted routing (swarmfleet): keep only the
            # requested pool's lanes; if the whole pool is quarantined
            # fall back to the full admissible set — the FleetManager
            # handles pool-level degradation before calling in here
            sel = [j for j in within if j in ok]
            ok = sel or ok
        if request.shard_hint is not None:
            j = request.shard_hint % len(self.lanes)
            if j in ok:
                return j, self.lanes[j]
            # hinted lane quarantined: deterministic remap so a
            # conversation's turns keep landing together (prefix reuse
            # on the fallback lane) until the home lane is re-admitted
            j = ok[request.shard_hint % len(ok)]
            return j, self.lanes[j]
        if self.tier_locator is not None:
            # tier-aware: land on the lane already holding the
            # conversation's warm pages (hint takes precedence above —
            # page custody beats payload locality)
            try:
                t = self.tier_locator(request)
            except Exception:
                t = None
            if t is not None:
                t = t % len(self.lanes)
                if t in ok:
                    return t, self.lanes[t]
        # DeServe-style tier pinning: CRITICAL (priority-0 in deadline
        # terms, numeric 3 here) traffic only ever lands on the fastest
        # admissible tier; batch/background is absorbed by slow lanes
        # via the weighted load score below.
        w = self.lane_weights
        if w is not None and request.priority >= 3:
            top = max(w[j] for j in ok)
            fast = [j for j in ok if w[j] >= top]
            ok = fast or ok
        # least-loaded admissible lane; racy reads are fine (load balance
        # is a heuristic, correctness never depends on it). Round-robin
        # tiebreak so an idle group still spreads arrivals.
        with self._rr_lock:
            self._rr += 1
            rot = self._rr
        loads = []
        for j in ok:
            e = self.lanes[j]
            load = len(e._queue) + sum(1 for s in e.slots if s.active)
            if w is not None:
                # effective load: a half-speed lane at load 2 is as
                # behind as a full-speed lane at load 4
                load = load / w[j]
            loads.append((load, (j + rot) % len(self.lanes), j, e))
        _, _, j, e = min(loads, key=lambda t: (t[0], t[1]))
        return j, e

    def _lane_for(self, request: GenRequest) -> Engine:
        return self._route(request)[1]

    def submit(self, request: GenRequest) -> str:
        if self.supervisor is not None:
            # adoption (deadline/retry budgets, migration tracking) +
            # health-aware routing; the supervisor dispatches through
            # the fleet (when present) or _route directly
            return self.supervisor.submit(request)
        if self.fleet is not None:
            if self.fleet.dispatch(request) is not None:
                return request.request_id
        return self._lane_for(request).submit(request)

    def cancel(self, request_id: str) -> bool:
        if self.supervisor is not None and self.supervisor.cancel(
                request_id):
            return True
        if self.fleet is not None and self.fleet.cancel(request_id):
            # transit-gap cancel: stage 1 retired on the prefill pool,
            # stage 2 not yet submitted — no engine knows the rid
            return True
        for e in self.lanes:
            if e.cancel(request_id):
                return True
        return False

    def generate_sync(self, prompt, sampling, timeout: float = 120.0):
        import threading as _t

        done = _t.Event()
        result: Dict[str, Any] = {}

        def on_done(rid, toks, reason):
            result["tokens"] = toks
            result["reason"] = reason
            done.set()

        self.submit(GenRequest(prompt=prompt, sampling=sampling,
                               on_done=on_done))
        if not done.wait(timeout):
            raise TimeoutError("generation timed out")
        return result["tokens"], result["reason"]

    # ------------------------------------------------------------- hooks

    @property
    def sentinel(self):
        return self._sentinel

    @sentinel.setter
    def sentinel(self, value) -> None:
        # every lane's loop drives window closes (maybe_tick is a
        # non-blocking single-closer election — concurrent tickers are
        # its design point)
        self._sentinel = value
        for e in self.lanes:
            e.sentinel = value

    @property
    def on_pool_pressure(self):
        return self.lanes[0].on_pool_pressure

    @on_pool_pressure.setter
    def on_pool_pressure(self, hook) -> None:
        for e in self.lanes:
            e.on_pool_pressure = hook

    def supports_rolling(self) -> bool:
        # page custody cannot span lanes; the serving layer already
        # refuses rolling on any multi-shard pool
        return False

    def pool_epoch(self) -> int:
        return sum(e.pool_epoch() for e in self.lanes)

    # -------------------------------------------------------------- info

    def stats(self) -> Dict[str, Any]:
        per = [e.stats() for e in self.lanes]
        out = {
            "active_slots": sum(p["active_slots"] for p in per),
            "max_batch": self.max_batch,
            "queued": sum(p["queued"] for p in per),
            "total_requests": sum(p["total_requests"] for p in per),
            "total_generated": sum(p["total_generated"] for p in per),
            "tokens_per_sec_60s": per[0]["tokens_per_sec_60s"],
            "latencies": per[0].get("latencies", {}),
            "lanes": len(per),
            "queued_by_lane": [p["queued"] for p in per],
            "active_by_lane": [p["active_slots"] for p in per],
            "ragged_prefill": bool(
                getattr(self.lanes[0], "_prefill_ragged_fused", None)
                is not None),
        }
        if self._prefix is not None:
            out["prefix_cache"] = self._prefix.stats()
        if self.fleet is not None:
            out["fleet"] = self.fleet.stats()
        if self.lane_weights is not None:
            out["lane_weights"] = list(self.lane_weights)
        if self.supervisor is not None:
            out["lane_states"] = [
                l["state"] for l in self.supervisor.status()["lanes"]]
        return out

    def attach_supervisor(self, **kwargs) -> Any:
        """Build, attach, and start a LaneSupervisor over this group
        (idempotent). The serving layer calls this unless
        SWARMDB_SUPERVISE=0."""
        if self.supervisor is None:
            from ..backend.supervisor import LaneSupervisor

            self.supervisor = LaneSupervisor(self, **kwargs).start()
        return self.supervisor


def build_lane_group(
    model_name_or_cfg: Any,
    mesh: Any,
    *,
    max_batch: int,
    max_seq: int = 1024,
    seed: int = 0,
    page_size: int = 16,
    kv_pool_tokens: Optional[int] = None,
    metrics: Optional[MetricsRegistry] = None,
    decode_chunk: int = 8,
    prefill_batch: Optional[int] = None,
    flight_dir: Optional[str] = None,
) -> ShardLaneGroup:
    """One paged single-device engine per mesh ``data`` device.

    Each lane's eager state (params, pools, PRNG keys, fed-token
    vectors) is built under ``jax.default_device(dev)`` and then
    COMMITTED to it (``Engine.pin_to_device``), so every jit the lane
    ever dispatches runs on ITS device — the per-shard admission overlap
    is then a property of the device streams, not of scheduler luck. Params are replicated across lanes (the definition
    of data parallelism); pools and prefix caches split N ways, same
    aggregate budget as the sharded pool."""
    from ..backend.service import build_backend_engine
    from ..models.configs import ModelConfig, get_config

    cfg = (model_name_or_cfg
           if isinstance(model_name_or_cfg, ModelConfig)
           else get_config(model_name_or_cfg))
    for ax in ("model", "expert", "pipe"):
        if mesh.shape.get(ax, 1) > 1:
            raise ValueError(
                "per-shard admission lanes require a pure-DP mesh "
                f"({ax} axis must be 1); TP/EP shard weights across "
                "devices, which per-device engines cannot")
    devices = list(mesh.devices.flat)
    n = len(devices)
    if max_batch % n:
        raise ValueError(f"max_batch {max_batch} must divide the lane "
                         f"count {n} (slot→lane affinity)")
    slots_per = max_batch // n
    metrics = metrics or MetricsRegistry()
    if kv_pool_tokens is None:
        # per-lane pool: full slot coverage + a prefix budget of one
        # full window per slot (TWICE the single-pool default's half):
        # lane caches are small and private — a conversation pinned to
        # lane d can only ever hit lane d's pages — so at the default
        # budget the per-lane LRU churns below the per-conversation
        # footprint and the hit rate collapses (measured 35% vs 47%)
        import os as _os

        from ..ops.paged_kv import pages_per_slot

        maxp = pages_per_slot(max_seq, page_size)
        lane_pool = slots_per * maxp * page_size + int(_os.environ.get(
            "SWARMDB_PREFIX_TOKENS", n * slots_per * max_seq)) // n
    else:
        lane_pool = max(1, kv_pool_tokens // n)
    lanes: List[Engine] = []
    for d, dev in enumerate(devices):
        with jax.default_device(dev):
            eng, _tok = build_backend_engine(
                cfg, max_batch=slots_per, max_seq=max_seq, seed=seed,
                decode_chunk=decode_chunk, paged=True,
                page_size=page_size,
                kv_pool_tokens=lane_pool,
                prefill_batch=prefill_batch, metrics=metrics,
                flight_dir=flight_dir,
            )
        eng.pin_to_device(dev)
        # page sanitizer (SWARMDB_PAGECHECK=1): label the lane's pool so
        # aliasing reports and the per-lane churn counters name lanes
        pagecheck = getattr(eng.paged.allocator, "pagecheck", None)
        if pagecheck is not None:
            pagecheck.set_lane(f"lane{d}")
        if n > 1:
            # distinct per-lane slot PRNG rows: lanes replicate PARAMS
            # (same seed), but reusing the same slot keys would make
            # temperature>0 sampling correlate across lanes at equal
            # (slot, position). Host-side rewrite only — the keys ride
            # every dispatch as a numpy argument.
            import numpy as _np

            from ..backend.sampling import make_slot_keys

            with jax.default_device(dev):
                eng.base_keys = make_slot_keys(seed + 7919 * (d + 1),
                                               slots_per)
            eng._base_keys_np = _np.array(eng.base_keys)
            eng._default_keys_np = eng._base_keys_np.copy()
        lanes.append(eng)
    info = LaneGroupInfo(cfg=cfg, mesh=mesh, data_size=n)
    return ShardLaneGroup(lanes, info, flight_dir=flight_dir)
