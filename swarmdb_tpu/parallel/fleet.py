"""swarmfleet: disaggregated prefill/decode lane pools (ISSUE 20).

swarmprof's kernel-level read says the two dominant serving workloads
are opposite roofline classes time-sharing the same lanes: ragged
prefill is compute-leaning (MFU 0.060) while resident decode is deeply
memory-bound (MFU 0.0026). This module removes that phase interference
the way prefill/decode disaggregation does (Scepsy; DeServe's tiered
engines): ``SWARMDB_FLEET=prefill:N,decode:M`` partitions a
``ShardLaneGroup``'s lanes into role-typed pools —

- **PREFILL lanes** run admission/ragged-prefill waves only. A staged
  request lands here with ``max_new_tokens=1`` + ``keep_pages``; the
  engine's prefill-drain retires it straight off the prefill sample
  (``Engine._drain_prefill_only``), and ``on_pages`` gathers the
  written KV to the transit ``HostPageStore`` (PR 19's warm payload —
  the ready-made handoff wire format, zstd-compressed under
  ``SWARMDB_TIER_ZSTD``).
- **DECODE lanes** run resident decode only. Stage 2 reserves device
  pages, rides the existing promote-insert + rolling-resume
  delta-prefill (the payload is bulk-inserted on the decode engine
  thread), and decodes the remaining budget. Greedy decode is
  bit-identical to the colocated engine: the prefill sample IS the fed
  token the colocated path reads as ``block[0, i]``.

DeServe-style tiering layers on top: ``SWARMDB_FLEET_TIERS`` gives
per-lane speed/reliability weights that ``ShardLaneGroup._route``
folds into load scores (a slow tier is weighted down, not excluded),
and priority-0 (CRITICAL) requests pin to the fastest admissible
decode lanes. Every fallback degrades to a correctness-preserving
colocated submit or an idempotent cold re-prefill — the fleet can lose
its payload, its pools, or a lane mid-handoff and the stream still
finishes (the supervisor's quarantine/migration replays staged
requests from the original prompt).

Default off: without ``SWARMDB_FLEET`` the group is bit-for-bit the
colocated design.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

logger = logging.getLogger("swarmdb_tpu.fleet")

__all__ = ["FleetManager", "build_fleet", "parse_fleet_spec",
           "parse_tier_weights"]


def parse_fleet_spec(n_lanes: int,
                     spec: Optional[str] = None
                     ) -> Optional[Dict[str, List[int]]]:
    """``prefill:N,decode:M`` -> pool map, or None (fleet off). A spec
    that does not exactly partition the lane count is REJECTED with a
    warning, not "fixed" — a silently resized pool would invalidate
    every capacity assumption the caller planned with."""
    if spec is None:
        spec = os.environ.get("SWARMDB_FLEET", "")
    spec = (spec or "").strip()
    if not spec:
        return None
    counts: Dict[str, int] = {}
    try:
        for part in spec.split(","):
            role, sep, cnt = part.strip().partition(":")
            role = role.strip().lower()
            if not sep or role not in ("prefill", "decode"):
                raise ValueError(part)
            counts[role] = int(cnt)
    except (ValueError, TypeError):
        logger.warning("SWARMDB_FLEET=%r is not 'prefill:N,decode:M'; "
                       "fleet disabled (colocated lanes)", spec)
        return None
    n_pre, n_dec = counts.get("prefill", 0), counts.get("decode", 0)
    if n_pre <= 0 or n_dec <= 0 or n_pre + n_dec != n_lanes:
        logger.warning(
            "SWARMDB_FLEET=%r does not partition %d lanes into non-empty "
            "prefill+decode pools; fleet disabled", spec, n_lanes)
        return None
    return {"prefill": list(range(n_pre)),
            "decode": list(range(n_pre, n_pre + n_dec))}


def parse_tier_weights(n_lanes: int,
                       spec: Optional[str] = None
                       ) -> Optional[List[float]]:
    """``SWARMDB_FLEET_TIERS=1.0,1.0,0.5,...`` -> per-lane speed/
    reliability weights (DeServe tiers). None = homogeneous."""
    if spec is None:
        spec = os.environ.get("SWARMDB_FLEET_TIERS", "")
    spec = (spec or "").strip()
    if not spec:
        return None
    try:
        w = [float(x) for x in spec.split(",")]
    except (ValueError, TypeError):
        logger.warning("SWARMDB_FLEET_TIERS=%r is not a float list; "
                       "ignoring tier weights", spec)
        return None
    if len(w) != n_lanes or any(x <= 0 for x in w):
        logger.warning("SWARMDB_FLEET_TIERS needs %d positive weights "
                       "(got %r); ignoring tier weights", n_lanes, spec)
        return None
    return w


def _transit_capacity_bytes() -> int:
    try:
        mb = float(os.environ.get("SWARMDB_FLEET_TRANSIT_MB", "256"))
    except ValueError:
        mb = 256.0
    return max(1, int(mb * (1 << 20)))


class _Handoff:
    """One staged request's cross-pool state. Callbacks close over the
    OBJECT (not the rid): a migration replay re-staging the same rid
    supersedes the dict entry, and every stale callback detects itself
    by identity check against ``_active[rid]``."""

    __slots__ = ("request", "prefill_idx", "tokens", "lps", "written",
                 "n_pages", "has_payload", "in_transit", "cancelled",
                 "t0")

    def __init__(self, request: Any, prefill_idx: int) -> None:
        self.request = request
        self.prefill_idx = prefill_idx
        self.tokens: List[int] = []
        self.lps: List[float] = []
        self.written = 0
        self.n_pages = 0
        self.has_payload = False
        self.in_transit = False
        self.cancelled = False
        self.t0 = 0.0


class FleetManager:
    """Pool map + two-stage handoff for one ``ShardLaneGroup``."""

    def __init__(self, group: Any, pools: Dict[str, List[int]],
                 weights: Optional[List[float]] = None,
                 store: Optional[Any] = None) -> None:
        from ..ops.host_pool import HostPageStore
        from ..utils.sync import make_lock

        self.group = group
        self.pools = pools
        self.weights = weights
        # the handoff wire format IS the warm-tier payload: the transit
        # store rides SWARMDB_TIER_ZSTD compression for free
        self.store = store if store is not None else HostPageStore(
            capacity_bytes=_transit_capacity_bytes(), label="fleet")
        self._lock = make_lock("parallel.fleet.FleetManager._lock")
        # swarmlint: guarded-by[self._lock]: _active
        self._active: Dict[str, _Handoff] = {}
        self._handoff_ms: "deque[float]" = deque(maxlen=1024)
        self.metrics = group.metrics
        self._role_by_lane: Dict[int, str] = {}
        for role, idxs in pools.items():
            for j in idxs:
                self._role_by_lane[j] = role
        for role in ("prefill", "decode"):
            for j in pools[role]:
                eng = group.lanes[j]
                eng._role = role
                prof = getattr(eng, "_prof", None)
                if prof is not None and hasattr(prof, "set_pool"):
                    prof.set_pool(role)

    # ------------------------------------------------------------- routing

    def lane_role(self, idx: int) -> Optional[str]:
        return self._role_by_lane.get(idx)

    def _admissible(self, role: str) -> List[int]:
        sup = self.group.supervisor
        idxs = self.pools[role]
        if sup is None:
            return list(idxs)
        return [j for j in idxs if sup.lane_admissible(j)]

    def _route_in(self, request: Any, role: str) -> Tuple[int, Any]:
        return self.group._route(request, within=self.pools[role])

    def _note(self, rid: str, idx: int) -> None:
        sup = self.group.supervisor
        if sup is not None and hasattr(sup, "note_lane"):
            sup.note_lane(rid, idx)

    def _submit_direct(self, request: Any, role: str) -> int:
        idx, eng = self._route_in(request, role)
        self._note(request.request_id, idx)
        eng.submit(request)
        return idx

    def _stageable(self, request: Any) -> bool:
        if (request.resume_pages is not None or request.keep_pages
                or request.promote_payload is not None
                or request.on_pages is not None):
            return False  # page custody cannot span the handoff
        if request.sampling.max_new_tokens < 2 or not request.prompt:
            return False
        dec = self.group.lanes[self.pools["decode"][0]]
        ps = dec.paged.page_size
        covering = -(-len(request.prompt) // ps)
        if not (0 < covering <= dec._prefix_pp_buckets[-1]):
            return False
        # stage 2 resubmits resume_len=len(prompt) + the 1-token tail
        return len(request.prompt) + 1 < dec.max_seq

    # ------------------------------------------------------------ dispatch

    def dispatch(self, request: Any) -> Optional[int]:
        """Route + submit one request through the fleet. Returns the
        lane index the request landed on (stage-1 lane for staged
        handoffs), or None to tell the caller to fall back to plain
        colocated routing (both pools unavailable)."""
        c = self.metrics.counters
        pre_ok = self._admissible("prefill")
        dec_ok = self._admissible("decode")
        if not pre_ok and not dec_ok:
            return None
        if not dec_ok or not pre_ok:
            # one pool fully quarantined: the surviving pool serves
            # colocated-style until the supervisor re-admits siblings
            role = "prefill" if pre_ok else "decode"
            c["fleet_colocated_fallback"].inc()
            return self._submit_direct(request, role)
        if (request.resume_pages is not None
                or request.promote_payload is not None
                or request.keep_pages):
            # rolling custody lives in ONE pool's pages: decode owns it
            c["fleet_direct_decode"].inc()
            return self._submit_direct(request, "decode")
        if request.sampling.max_new_tokens <= 1:
            # admission-only work (classification heads, probes routed
            # through the group): the prefill drain retires it in place
            c["fleet_direct_prefill"].inc()
            return self._submit_direct(request, "prefill")
        if not self._stageable(request):
            c["fleet_colocated_fallback"].inc()
            return self._submit_direct(request, "decode")
        return self._stage1_submit(request)

    # ------------------------------------------------------------- stage 1

    def _stage1_submit(self, request: Any) -> int:
        rid = request.request_id
        idx, eng = self._route_in(request, "prefill")
        h = _Handoff(request, idx)
        with self._lock:
            old = self._active.pop(rid, None)
            self._active[rid] = h
        if old is not None:
            # migration replay re-staged the same rid: the old attempt's
            # payload (if any) is stale — drop it and clear its guard
            self._drop_payload(old)
        sp = request.sampling
        stage1 = dataclasses.replace(
            request,
            sampling=dataclasses.replace(sp, max_new_tokens=1),
            keep_pages=True,
            on_pages=lambda r, pages, written, tail:
                self._on_pages(h, r, pages, written, tail),
            on_done=lambda r, toks, reason:
                self._stage1_done(h, r, toks, reason, stage1),
        )
        self._note(rid, idx)
        try:
            eng.submit(stage1)
        except Exception:
            with self._lock:
                if self._active.get(rid) is h:
                    del self._active[rid]
            raise
        return idx

    def _on_pages(self, h: _Handoff, rid: str, pages: List[int],
                  written: int, tail: List[int]) -> None:
        """Prefill ENGINE thread, inside ``_retire``: gather the staged
        request's written KV to the transit store and free the device
        pages — the exact demote sequence ``backend/tiering.py`` runs
        (pagecheck ``host_resident`` transit state included)."""
        from ..ops.paged_kv import pool_gather_pages

        eng = self.group.lanes[h.prefill_idx]
        with self._lock:
            stale = self._active.get(rid) is not h or h.cancelled
        if stale or not pages or written <= 0:
            if pages:
                eng.rolling_free(pages)
            return
        pc = getattr(eng, "_pagecheck", None)
        stored = False
        try:
            if pc is not None:
                pc.on_demote(pages, rid)
            k_pay = pool_gather_pages(eng.cache["k"], pages)
            v_pay = pool_gather_pages(eng.cache["v"], pages)
            evicted = self.store.put(rid, k_pay, v_pay, len(pages),
                                     written)
            stored = rid not in evicted
            for ek in evicted:
                if ek != rid:
                    self._evict_handoff(ek)
        except Exception:
            logger.exception("fleet handoff gather failed for %s", rid)
        finally:
            eng.rolling_free(pages)
            if not stored and pc is not None:
                pc.on_host_drop(rid)
        if stored:
            h.written = written
            h.n_pages = len(pages)
            h.has_payload = True

    def _evict_handoff(self, rid: str) -> None:
        """Another handoff's payload was capacity-evicted from the
        transit store mid-flight: its stage 2 will cold-replay. Clear
        its prefill-pool pagecheck guard now."""
        with self._lock:
            victim = self._active.get(rid)
        if victim is None:
            return
        victim.has_payload = False
        pc = getattr(self.group.lanes[victim.prefill_idx],
                     "_pagecheck", None)
        if pc is not None:
            pc.on_host_drop(rid)

    def _stage1_done(self, h: _Handoff, rid: str, toks: List[int],
                     reason: str, stage1: Any) -> None:
        """Prefill ENGINE thread, inside ``_retire``'s on_done guard —
        must NEVER raise. Builds + submits stage 2 (or a fallback)."""
        req = h.request
        with self._lock:
            if self._active.get(rid) is not h:
                return  # superseded by a migration replay: stale attempt
            if h.cancelled:
                return  # cancel already surfaced on_done
            h.in_transit = True
            h.t0 = time.monotonic()
        try:
            h.tokens = list(toks)
            lps = req.metadata.get("logprobs")
            h.lps = list(lps) if isinstance(lps, list) else []
            if reason == "length" and toks:
                self._submit_stage2(h, rid)
                return
            # eos at the first token, cancel, shed, engine_error, ...:
            # the stream is over (or the supervisor will replay it) —
            # forward the stage-1 verdict untouched
            self._drop_payload(h)
            self._hand_routing(h, stage1)
            self._finish(h, rid, list(toks), reason)
        except Exception:
            logger.exception("fleet stage-2 build failed for %s", rid)
            try:
                self._cold_replay(h, rid)
            except Exception:
                logger.exception("fleet cold replay failed for %s", rid)
                self._finish(h, rid, list(h.tokens), "engine_error")

    # ------------------------------------------------------------- stage 2

    def _submit_stage2(self, h: _Handoff, rid: str) -> None:
        c = self.metrics.counters
        req = h.request
        entry = self.store.pop(rid)
        pre_pc = getattr(self.group.lanes[h.prefill_idx],
                         "_pagecheck", None)
        if pre_pc is not None:
            # custody leaves the prefill pool whether or not the payload
            # survived (a miss means it was evicted → cold replay)
            pre_pc.on_host_drop(rid)
        if entry is None or not h.has_payload:
            c["fleet_handoff_fallbacks"].inc()
            self._cold_replay(h, rid)
            return
        dec_ok = self._admissible("decode")
        if not dec_ok:
            c["fleet_handoff_fallbacks"].inc()
            self._cold_replay(h, rid)
            return
        idx, eng = self.group._route(req, within=dec_ok)
        alloc = eng.paged.allocator
        ids = alloc.reserve(entry.n_pages)
        if len(ids) < entry.n_pages:
            alloc.add_free(ids)
            c["fleet_handoff_fallbacks"].inc()
            self._cold_replay(h, rid)
            return
        pc = getattr(eng, "_pagecheck", None)
        if pc is not None:
            pc.on_promote(ids, rid)
        sp = req.sampling
        epoch = eng.pool_epoch()
        stage2 = dataclasses.replace(
            req,
            prompt=list(h.tokens),
            sampling=dataclasses.replace(
                sp, max_new_tokens=sp.max_new_tokens - len(h.tokens)),
            resume_pages=ids, resume_len=h.written, resume_epoch=epoch,
            promote_payload=(entry.k, entry.v),
            keep_pages=False, on_pages=None,
            on_done=lambda r, toks, reason:
                self._stage2_done(h, eng, ids, epoch, r, toks, reason,
                                  stage2),
        )
        with self._lock:
            if h.cancelled:
                # cancelled in the transit gap: cancel() surfaced
                # on_done already — just return the promoted pages
                eng.rolling_free(ids)
                return
            h.in_transit = False
        self._note(rid, idx)
        try:
            eng.submit(stage2)
        except Exception:
            logger.exception("fleet stage-2 submit failed for %s", rid)
            eng.rolling_free(ids)
            c["fleet_handoff_fallbacks"].inc()
            self._cold_replay(h, rid)
            return
        dt_ms = (time.monotonic() - h.t0) * 1e3
        with self._lock:
            self._handoff_ms.append(dt_ms)
        c["fleet_handoffs"].inc()
        self.metrics.latencies["fleet_handoff_s"].observe(dt_ms / 1e3)

    def _stage2_done(self, h: _Handoff, eng: Any, ids: List[int],
                     epoch: int, rid: str, toks: List[int],
                     reason: str, stage2: Any) -> None:
        """Decode ENGINE thread, inside ``_retire``: release transit
        custody of the resumed pages and surface the merged stream."""
        self._hand_routing(h, stage2)
        if epoch == eng.pool_epoch():
            try:
                eng.rolling_free(ids)
            except Exception:
                logger.exception("fleet resume-page free failed for %s",
                                 rid)
        req = h.request
        lps = req.metadata.get("logprobs")
        if isinstance(lps, list):
            req.metadata["logprobs"] = h.lps + lps
        self._finish(h, rid, list(h.tokens) + list(toks), reason)

    # ----------------------------------------------------------- fallbacks

    def _cold_replay(self, h: _Handoff, rid: str) -> None:
        """The payload is gone (evicted / reserve shortfall / submit
        raise): re-prefill idempotently from the original prompt + the
        already-emitted tokens — greedy-identical continuation, exactly
        the supervisor's migration discipline."""
        self._drop_payload(h)
        req = h.request
        emitted = list(h.tokens)
        sp = req.sampling
        left = sp.max_new_tokens - len(emitted)
        if left <= 0:
            self._finish(h, rid, emitted, "length")
            return
        replay = dataclasses.replace(
            req,
            prompt=list(req.prompt) + emitted,
            sampling=dataclasses.replace(sp, max_new_tokens=left),
            resume_pages=None, resume_len=0, resume_epoch=None,
            promote_payload=None, keep_pages=False, on_pages=None,
            on_done=lambda r, toks, reason: (
                self._hand_routing(h, replay),
                self._finish(h, r, emitted + list(toks), reason)),
        )
        dec_ok = self._admissible("decode")
        pool = dec_ok or self._admissible("prefill") \
            or list(range(len(self.group.lanes)))
        with self._lock:
            if h.cancelled:
                return
            h.in_transit = False
        idx, eng = self.group._route(replay, within=pool)
        self._note(rid, idx)
        eng.submit(replay)

    def _drop_payload(self, h: _Handoff) -> None:
        rid = h.request.request_id
        if self.store.drop(rid) or h.has_payload:
            pc = getattr(self.group.lanes[h.prefill_idx],
                         "_pagecheck", None)
            if pc is not None:
                pc.on_host_drop(rid)
        h.has_payload = False

    @staticmethod
    def _hand_routing(h: _Handoff, staged: Any) -> None:
        """A configuration that routes: the engine wrote its record on the
        staged copy it served, so the caller's request takes the record
        of the stage that ended the stream. A stage 2 resumed from the
        transit store's pages, whose routing did not travel with them:
        its rows are those it computed and ``routing_complete`` is False
        (the engine counted it); a cold replay recomputed every position
        and is complete."""
        h.request.routing = staged.routing
        h.request.routing_complete = staged.routing_complete

    def _finish(self, h: _Handoff, rid: str, tokens: List[int],
                reason: str) -> None:
        with self._lock:
            if self._active.get(rid) is h:
                del self._active[rid]
        req = h.request
        if req.on_done is not None:
            try:
                req.on_done(rid, tokens, reason)
            except Exception:
                logger.exception("fleet on_done failed for %s", rid)

    # -------------------------------------------------------------- cancel

    def cancel(self, request_id: str) -> bool:
        """Cancel a request parked in the transit gap (stage 1 retired,
        stage 2 not yet submitted) — the one moment no engine knows the
        rid. Engine-resident stages cancel through the normal per-lane
        path (same rid)."""
        with self._lock:
            h = self._active.get(request_id)
            if h is None or not h.in_transit or h.cancelled:
                return False
            h.cancelled = True
        self._drop_payload(h)
        self._finish(h, request_id, list(h.tokens), "cancelled")
        return True

    # --------------------------------------------------------------- intro

    def stats(self) -> Dict[str, Any]:
        c = self.metrics.counters
        with self._lock:
            lat = sorted(self._handoff_ms)
            active = len(self._active)
        def pct(p: float) -> Optional[float]:
            if not lat:
                return None
            return round(lat[min(len(lat) - 1,
                                 int(p * (len(lat) - 1)))], 3)
        return {
            "pools": {r: list(v) for r, v in self.pools.items()},
            "pool_sizes": {r: len(v) for r, v in self.pools.items()},
            "weights": list(self.weights) if self.weights else None,
            "handoffs": c["fleet_handoffs"].value,
            "handoff_fallbacks": c["fleet_handoff_fallbacks"].value,
            "direct_prefill": c["fleet_direct_prefill"].value,
            "direct_decode": c["fleet_direct_decode"].value,
            "colocated_fallback": c["fleet_colocated_fallback"].value,
            "in_flight": active,
            "handoff_ms_p50": pct(0.50),
            "handoff_ms_p95": pct(0.95),
            "transit_store": self.store.stats(),
        }


def build_fleet(group: Any) -> Optional[FleetManager]:
    """Parse the env surface and wire a FleetManager onto ``group`` —
    or None (default): colocated, bit-for-bit untouched."""
    n = len(group.lanes)
    pools = parse_fleet_spec(n)
    if pools is None:
        return None
    for d in pools["decode"]:
        eng = group.lanes[d]
        if (eng.paged is None
                or getattr(eng, "_prefill_paged_resume_fused", None)
                is None):
            logger.warning(
                "SWARMDB_FLEET disabled: decode lane %d lacks the "
                "rolling-resume prefill (paged + prefix engines only)", d)
            return None
    return FleetManager(group, pools, parse_tier_weights(n))
