"""Lane supervision, retryable request migration, and deadline/retry
budgets (ISSUE 9 tentpole).

PR 4 made the *broker* self-healing; PR 7 made lanes the unit of
execution. This module closes the remaining gap: the SERVING path failed
open — a crashed decode thread, a wedged device dispatch, or an
exhausted page pool turned into hung streams and lost requests. The
supervisor applies the HA control plane's two-signal failure-detection
pattern (``ha/detector.py``) to engine lanes and turns every engine-side
loss into a bounded, deadline-aware retry instead of a client-visible
failure (DeServe's serve-over-unreliable-capacity discipline,
PAPERS.md; ROADMAP item 5's "engine loss handled by the detector +
requeue").

Two independent signals feed one verdict per lane:

- **In-band beats** — the decode loop stamps ``Engine._beat_mono`` once
  per iteration (idle waits included) and the emission-ring callback
  stamps it per chunk, so a lane mid-session still beats. A wedged
  device dispatch stops the beats while the thread stays alive.
- **Out-of-band probe** — thread liveness (``Engine.alive()``) plus,
  during recovery, real probe generations through the lane.

States: ``ALIVE`` → ``SUSPECT`` (beats stale for
``SWARMDB_LANE_SUSPECT_S``) → ``QUARANTINED`` (stale for
``SWARMDB_LANE_QUARANTINE_S``, or the thread died). A quarantined lane
stops taking admissions (routing excludes it), its queued + in-flight
requests are **migrated** to sibling lanes, and a background probe
re-admits it after ``SWARMDB_LANE_PROBE_N`` clean generations.

Migration is an idempotent re-prefill: the replay's prompt is the
original prompt plus every token already emitted to the client, so the
sibling lane's decode continues exactly where the stream stopped (anchor
heads + the prefix cache make the replay prefill cheap). Duplicate
suppression is structural: each attempt's callbacks are bound to an
attempt number, and the tracker drops anything from a stale attempt —
a slow (not dead) lane that revives after migration can never re-emit a
chunk the client already saw. With greedy sampling the replayed stream
is bit-identical to an uninterrupted run (test_serving_chaos proves it
at every chunk boundary).

Budgets: every adopted request carries an absolute deadline
(``SWARMDB_REQ_DEADLINE_S``) and a bounded retry budget
(``SWARMDB_REQ_RETRIES``). Retryable finishes (``engine.py
RETRYABLE_REASONS`` — the ``BrokerError.retryable`` contract applied to
serving) requeue with jittered exponential backoff; everything else, and
anything that cannot finish before its deadline, surfaces immediately.

``SWARMDB_SUPERVISE=0`` disables the supervisor entirely (the serving
layer falls back to the pre-ISSUE-9 watchdog restart).
"""

from __future__ import annotations

import dataclasses
import enum
import logging
import os
import random
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..obs import TRACER, FlightRecorder, procwatch
from ..utils.metrics import MetricsRegistry
from .engine import Engine, GenRequest, is_retryable_reason
from ..utils.sync import make_lock

logger = logging.getLogger("swarmdb_tpu.supervisor")

__all__ = ["LaneState", "LaneSupervisor"]


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        logger.warning("%s=%r is not a float; using %g", name,
                       os.environ.get(name), default)
        return default


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        logger.warning("%s=%r is not an int; using %d", name,
                       os.environ.get(name), default)
        return default


class LaneState(enum.IntEnum):
    ALIVE = 0
    SUSPECT = 1
    QUARANTINED = 2


@dataclasses.dataclass
class _LaneHealth:
    state: LaneState = LaneState.ALIVE
    since: float = dataclasses.field(default_factory=time.monotonic)
    quarantines: int = 0
    restarts: int = 0
    restart_fails: int = 0
    last_restart: float = 0.0
    clean_probes: int = 0


class _Tracked:
    """One supervised request across its attempts (migrations/retries).

    ``attempt`` is the dedupe key: every wrapped callback is bound to the
    attempt it was created for, and anything arriving from a stale
    attempt is dropped under the tracker lock — the emitted-token stream
    the CLIENT sees is therefore append-only and duplicate-free no
    matter how a lane dies or revives mid-chunk.
    """

    __slots__ = ("request", "prompt", "user_on_token", "user_on_done",
                 "emitted", "attempt", "lane", "done", "retries_left",
                 "migrations_left", "deadline", "retried", "migrated",
                 "lock", "retry_timer")

    def __init__(self, request: GenRequest, migrations: int) -> None:
        self.request = request
        self.prompt = list(request.prompt)
        self.user_on_token = request.on_token
        self.user_on_done = request.on_done
        self.emitted: List[int] = []
        self.attempt = 0
        self.lane = 0
        self.done = False
        self.retries_left = request.retries_left
        self.migrations_left = migrations
        self.deadline = request.deadline
        self.retried = 0
        self.migrated = 0
        self.lock = make_lock("backend.supervisor._Tracked.lock")
        self.retry_timer: Optional[threading.Timer] = None

    @property
    def migratable(self) -> bool:
        # rolling-KV requests reference pages in ONE lane's pool; their
        # context cannot be rebuilt here (the serving layer's registry
        # restarts the conversation next turn instead)
        return (self.request.resume_pages is None
                and not self.request.keep_pages)


class LaneSupervisor:
    """Supervises the lanes of a ``ShardLaneGroup`` (or one bare
    ``Engine``): health verdicts, request migration, retry/deadline
    budgets, and quarantined-lane recovery."""

    def __init__(self, engine: Any, *,
                 metrics: Optional[MetricsRegistry] = None,
                 flight: Optional[FlightRecorder] = None,
                 suspect_s: Optional[float] = None,
                 quarantine_s: Optional[float] = None,
                 poll_s: Optional[float] = None,
                 probe_clean_n: Optional[int] = None,
                 probe_timeout_s: Optional[float] = None,
                 deadline_s: Optional[float] = None,
                 retries: Optional[int] = None) -> None:
        self.group = engine if hasattr(engine, "lanes") else None
        self.lanes: List[Engine] = (list(self.group.lanes) if self.group
                                    else [engine])
        self.metrics = metrics or self.lanes[0].metrics
        self.flight = flight if flight is not None else \
            (self.group.flight if self.group else self.lanes[0].flight)
        self.suspect_s = (suspect_s if suspect_s is not None
                          else _env_float("SWARMDB_LANE_SUSPECT_S", 2.0))
        self.quarantine_s = (
            quarantine_s if quarantine_s is not None
            else _env_float("SWARMDB_LANE_QUARANTINE_S",
                            2.0 * self.suspect_s))
        self.poll_s = poll_s if poll_s is not None else self.suspect_s / 4.0
        self.probe_clean_n = (probe_clean_n if probe_clean_n is not None
                              else _env_int("SWARMDB_LANE_PROBE_N", 3))
        self.probe_timeout_s = (
            probe_timeout_s if probe_timeout_s is not None
            else _env_float("SWARMDB_LANE_PROBE_TIMEOUT_S", 15.0))
        # generous default: the deadline exists to bound HANGS (a lost
        # stream must fail visibly), not to police slow-but-progressing
        # requests — a cold compile alone can cost a minute and a half
        self.deadline_s = (deadline_s if deadline_s is not None
                           else _env_float("SWARMDB_REQ_DEADLINE_S", 600.0))
        self.retries = (retries if retries is not None
                        else _env_int("SWARMDB_REQ_RETRIES", 2))
        self.migrations = _env_int("SWARMDB_REQ_MIGRATIONS", 3)
        self.backoff_s = _env_float("SWARMDB_RETRY_BACKOFF_S", 0.05)
        self.restart_backoff_s = _env_float(
            "SWARMDB_LANE_RESTART_BACKOFF_S", 0.25)
        # in-step stall grace: a lane whose loop is INSIDE a step (a
        # first-traffic XLA compile, a long legitimate dispatch) may
        # starve beats for this long before the stall reads as a wedge.
        # Stalls outside a step get no grace.
        self.dispatch_grace_s = _env_float(
            "SWARMDB_LANE_DISPATCH_GRACE_S", 180.0)
        self.storm_n = _env_int("SWARMDB_RETRY_STORM_N", 8)
        self.health: List[_LaneHealth] = [
            _LaneHealth() for _ in self.lanes]
        # swarmlint: guarded-by[self._lock]: _tracked
        self._tracked: Dict[str, _Tracked] = {}
        self._lock = make_lock("backend.supervisor.LaneSupervisor._lock")
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._prev_retried = 0
        self._storming = False

    # ------------------------------------------------------------ lifecycle

    def start(self) -> "LaneSupervisor":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._watch_loop, daemon=True,
                name="swarmdb-lane-supervisor")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        with self._lock:
            trackers = list(self._tracked.values())
        for tr in trackers:
            with tr.lock:
                t = tr.retry_timer
            if t is not None:
                t.cancel()

    # ----------------------------------------------------------- submission

    def submit(self, request: GenRequest) -> str:
        """Adopt + route + submit one request. The returned id is stable
        across migrations/retries (replays reuse it), so cancel and
        stream identity keep working from the caller's side."""
        tr = self._adopt(request)
        # track BEFORE dispatching: a fleet handoff can move the request
        # across pools (note_lane) while the submit call is still in
        # flight, and those updates need the tracker registered
        with self._lock:
            self._tracked[request.request_id] = tr
        try:
            self._dispatch(request)
            return request.request_id
        except Exception:
            with self._lock:
                self._tracked.pop(request.request_id, None)
            raise

    def _adopt(self, request: GenRequest) -> _Tracked:
        """Stamp default budgets and bind attempt-scoped callbacks."""
        if request.deadline is None and self.deadline_s > 0:
            request.deadline = request.submitted_at + self.deadline_s
        if request.retries_left == 0:
            request.retries_left = max(0, self.retries)
        elif request.retries_left < 0:
            request.retries_left = 0
        tr = _Tracked(request, self.migrations)
        request.on_token, request.on_done = self._wrap(tr, 0)
        return tr

    def cancel(self, request_id: str) -> bool:
        """Cancel a supervised request wherever it currently lives —
        including a retry-timer wait, which no engine knows about."""
        with self._lock:
            tr = self._tracked.get(request_id)
        if tr is None:
            return False
        timer = None
        with tr.lock:
            if tr.done:
                return False
            timer = tr.retry_timer
            tr.retry_timer = None
        if timer is not None:
            timer.cancel()
            self._finalize(tr, "cancelled")
            return True
        # let the engine's cancel flow through the wrapped on_done
        for eng in self.lanes:
            if eng.cancel(request_id):
                return True
        return False

    # ------------------------------------------------------------- routing

    def lane_admissible(self, idx: int) -> bool:
        return self.health[idx].state != LaneState.QUARANTINED

    def _route(self, request: GenRequest) -> Tuple[int, Engine]:
        if self.group is not None:
            return self.group._route(request)
        return 0, self.lanes[0]

    def _dispatch(self, request: GenRequest) -> int:
        """Route + submit one request (or replay). With a fleet attached
        (swarmfleet role pools) the FleetManager owns placement — staged
        prefill→decode handoffs included; it reports lane positions back
        through note_lane. Otherwise: classic health-aware route."""
        fleet = getattr(self.group, "fleet", None) \
            if self.group is not None else None
        if fleet is not None:
            idx = fleet.dispatch(request)
            if idx is not None:
                return idx
        idx, eng = self._route(request)
        self.note_lane(request.request_id, idx)
        eng.submit(request)
        return idx

    def note_lane(self, request_id: str, idx: int) -> None:
        """Record where a tracked request currently lives. The fleet
        calls this at every stage transition (prefill lane, then decode
        lane) so quarantine scans migrate cross-pool requests from the
        lane they actually occupy."""
        with self._lock:
            tr = self._tracked.get(request_id)
        if tr is None:
            return
        with tr.lock:
            if not tr.done:
                tr.lane = idx

    # ------------------------------------------------------------ wrapping

    def _wrap(self, tr: _Tracked, attempt: int):
        def on_token(rid: str, token: int) -> None:
            with tr.lock:
                if tr.done or attempt != tr.attempt:
                    return  # stale attempt: already migrated past this
                tr.emitted.append(token)
                cb = tr.user_on_token
            if cb is not None:
                cb(rid, token)

        def on_done(rid: str, tokens: List[int], reason: str) -> None:
            self._attempt_done(tr, attempt, reason)

        return on_token, on_done

    def _attempt_done(self, tr: _Tracked, attempt: int,
                      reason: str) -> None:
        """One attempt finished. Final reasons (and exhausted budgets)
        surface to the user with the full cross-attempt token stream;
        retryable ones requeue with jittered exponential backoff."""
        retry_delay = None
        with tr.lock:
            if tr.done or attempt != tr.attempt:
                return  # stale attempt (migrated away / already final)
            sp = tr.request.sampling
            if (is_retryable_reason(reason)
                    and len(tr.emitted) >= sp.max_new_tokens):
                # the stream actually completed before the lane died —
                # nothing left to generate, surface success
                reason = "length"
            if (is_retryable_reason(reason) and tr.retries_left > 0
                    and not self._stop.is_set()):
                delay = (self.backoff_s * (2 ** tr.retried)
                         * (1.0 + random.random()))
                if (tr.deadline is None
                        or time.time() + delay < tr.deadline):
                    tr.retries_left -= 1
                    tr.retried += 1
                    tr.attempt += 1
                    retry_delay = delay
                    next_attempt = tr.attempt
        if retry_delay is None:
            self._finalize(tr, reason)
            return
        self.metrics.counters["requests_retried"].inc()
        self.flight.record_event(
            {"kind": "request.retried", "rid": tr.request.request_id,
             "reason": reason, "attempt": next_attempt,
             "backoff_s": round(retry_delay, 4)})
        timer = threading.Timer(retry_delay, self._resubmit,
                                args=(tr, next_attempt))
        timer.daemon = True
        with tr.lock:
            if tr.done:  # cancelled while we built the timer
                return
            tr.retry_timer = timer
        timer.start()

    def _resubmit(self, tr: _Tracked, attempt: int) -> None:
        """Timer target: requeue the replay on a healthy lane."""
        with tr.lock:
            if tr.done or attempt != tr.attempt:
                return
            tr.retry_timer = None
            replay = self._build_replay(tr, attempt)
        with tr.lock:
            if tr.done or attempt != tr.attempt:
                return
        try:
            self._dispatch(replay)
        except Exception:
            logger.exception("retry resubmit failed for %s",
                             tr.request.request_id)
            self._finalize(tr, "engine_error", surface=True)

    def _build_replay(self, tr: _Tracked, attempt: int) -> GenRequest:
        """Idempotent re-prefill: prompt = original prompt + everything
        already emitted, decode budget reduced by the same amount. The
        anchor head + prefix cache make the replayed prefix cheap, and
        the emitted-token offset guarantees the client stream continues
        without a duplicated or missing chunk (caller holds tr.lock)."""
        emitted = list(tr.emitted)
        sp = tr.request.sampling
        replay = dataclasses.replace(
            tr.request,
            prompt=tr.prompt + emitted,
            sampling=dataclasses.replace(
                sp, max_new_tokens=max(1, sp.max_new_tokens - len(emitted))),
            submitted_at=time.time(),
            resume_pages=None, resume_len=0, resume_epoch=None,
            keep_pages=False, on_pages=None, promote_payload=None,
        )
        replay.on_token, replay.on_done = self._wrap(tr, attempt)
        return replay

    def _finalize(self, tr: _Tracked, reason: str,
                  surface: bool = True) -> None:
        with tr.lock:
            if tr.done:
                return
            tr.done = True
            timer, tr.retry_timer = tr.retry_timer, None
            tokens = list(tr.emitted)
            cb = tr.user_on_done
        if timer is not None:
            timer.cancel()
        with self._lock:
            self._tracked.pop(tr.request.request_id, None)
        if surface and cb is not None:
            try:
                cb(tr.request.request_id, tokens, reason)
            except Exception:
                logger.exception("on_done callback failed for %s",
                                 tr.request.request_id)

    # ------------------------------------------------------------ verdicts

    # swarmlint: heartbeat
    def _evaluate(self, eng: Engine, now: float) -> LaneState:
        # pure arithmetic over the lane's single-writer stamps (the
        # detector discipline of ha/detector.py): no locks, no I/O
        if eng._thread is None:
            # never started, or deliberately stopped (Engine.stop joins
            # then clears the slot; a CRASHED thread stays referenced):
            # not running is not a failure — supervising it would fight
            # the serving lifecycle (warmup runs BEFORE start, and a
            # supervisor-triggered restart there races warmup's donated
            # buffers)
            return LaneState.ALIVE
        if not eng.alive():
            return LaneState.QUARANTINED
        age = eng.beat_age_s(now)
        if age < self.suspect_s:
            return LaneState.ALIVE
        if eng._in_step and age < self.dispatch_grace_s:
            # stalled INSIDE a step: plausibly a cold compile, not a
            # wedge — hold at SUSPECT for the grace window
            return LaneState.SUSPECT
        if age < self.quarantine_s:
            return LaneState.SUSPECT
        return LaneState.QUARANTINED

    def _watch_loop(self) -> None:
        while not self._stop.is_set():
            now = time.monotonic()
            for idx, eng in enumerate(self.lanes):
                h = self.health[idx]
                if h.state == LaneState.QUARANTINED:
                    self._try_readmit(idx, eng, h)
                    continue
                new = self._evaluate(eng, now)
                if new != h.state:
                    self._transition(idx, eng, h, new)
            self._sweep_deadlines()
            self._detect_retry_storm()
            self._stop.wait(self.poll_s)

    def _transition(self, idx: int, eng: Engine, h: _LaneHealth,
                    new: LaneState) -> None:
        old, h.state = h.state, new
        h.since = time.monotonic()
        age = eng.beat_age_s()
        # how much of that age the whole process stood still, by the
        # watcher's record (obs/procwatch.py): the lane is then not to blame
        late = round(procwatch.process_late_s(age), 4)
        logger.warning("lane %d: %s -> %s (beat age %.3fs, of which the "
                       "process stood still %.3fs, thread %s)",
                       idx, old.name, new.name, age, late,
                       "alive" if eng.alive() else "dead")
        self.flight.record_event(
            {"kind": f"lane.{new.name.lower()}", "lane": idx,
             "beat_age_s": round(age, 4), "process_late_s": late,
             "thread_alive": eng.alive()})
        TRACER.instant(f"lane.{new.name.lower()}", cat="supervisor",
                       args={"lane": idx, "process_late_s": late})
        if new == LaneState.QUARANTINED:
            h.quarantines += 1
            h.clean_probes = 0
            self.metrics.counters["lane_quarantines"].inc()
            self._migrate_lane(idx)

    # ----------------------------------------------------------- migration

    def _migrate_lane(self, idx: int) -> None:
        """Move every supervised request assigned to a quarantined lane
        onto healthy siblings. Order matters: the attempt bump happens
        FIRST (under the tracker lock), so anything the dying lane still
        emits or finalizes for the old attempt is dropped, THEN the old
        copy is cancelled (best-effort), THEN the replay lands on a
        sibling."""
        with self._lock:
            victims = [tr for tr in self._tracked.values()
                       if tr.lane == idx]
        moved = 0
        for tr in victims:
            complete = False
            with tr.lock:
                if tr.done or tr.lane != idx:
                    continue
                if len(tr.emitted) >= tr.request.sampling.max_new_tokens:
                    # the stream already finished generating — the lane
                    # died between the last emission and its retirement
                    # bookkeeping. Replaying would decode an EXTRA token;
                    # surface success instead.
                    tr.attempt += 1  # stale-proof the dead lane's on_done
                    complete = True
                elif (not tr.migratable or tr.migrations_left <= 0
                        or (tr.deadline is not None
                            and time.time() >= tr.deadline)):
                    bump = None
                else:
                    tr.migrations_left -= 1
                    tr.migrated += 1
                    tr.attempt += 1
                    bump = tr.attempt
            # cancel outside the tracker lock: engine.cancel can fire the
            # (now stale) wrapped on_done synchronously
            try:
                self.lanes[idx].cancel(tr.request.request_id)
            except Exception:
                logger.exception("cancel on quarantined lane %d failed",
                                 idx)
            if complete:
                self._finalize(tr, "length")
                continue
            if bump is None:
                self._finalize(tr, "lane_quarantined")
                continue
            with tr.lock:
                if tr.done or tr.attempt != bump:
                    continue
                replay = self._build_replay(tr, bump)
            try:
                new_idx = self._dispatch(replay)
                moved += 1
                self.metrics.counters["requests_migrated"].inc()
                self.flight.record_event(
                    {"kind": "request.migrated",
                     "rid": tr.request.request_id,
                     "from_lane": idx, "to_lane": new_idx,
                     "emitted": len(replay.prompt) - len(tr.prompt)})
            except Exception:
                logger.exception("migration resubmit failed for %s",
                                 tr.request.request_id)
                self._finalize(tr, "engine_error")
        if moved:
            logger.warning("lane %d quarantined: migrated %d request(s) "
                           "to sibling lanes", idx, moved)

    # ------------------------------------------------------------ recovery

    def _try_readmit(self, idx: int, eng: Engine, h: _LaneHealth) -> None:
        """Background recovery of a quarantined lane: restart a dead
        thread (with backoff), then require fresh beats plus
        ``probe_clean_n`` clean probe generations before re-admitting."""
        now = time.monotonic()
        if not eng.alive():
            h.clean_probes = 0
            wait = self.restart_backoff_s * (2 ** min(h.restart_fails, 5))
            if now - h.last_restart < wait:
                return
            h.last_restart = now
            try:
                eng.restart()
                h.restarts += 1
                h.restart_fails = 0
            except Exception:
                h.restart_fails += 1
                logger.exception("lane %d restart failed (attempt %d)",
                                 idx, h.restart_fails)
            return
        if eng.beat_age_s() >= self.suspect_s:
            # thread alive but still not stepping (wedge not yet healed)
            h.clean_probes = 0
            return
        if self._probe_lane(idx, eng, h):
            h.state = LaneState.ALIVE
            h.since = time.monotonic()
            self.metrics.counters["lane_readmissions"].inc()
            self.flight.record_event(
                {"kind": "lane.readmitted", "lane": idx,
                 "after_s": round(time.monotonic() - h.since, 3),
                 "restarts": h.restarts})
            TRACER.instant("lane.readmitted", cat="supervisor",
                           args={"lane": idx})
            logger.warning("lane %d re-admitted after %d clean probes",
                           idx, self.probe_clean_n)

    # swarmlint: retry
    def _probe_lane(self, idx: int, eng: Engine, h: _LaneHealth) -> bool:
        """Run the remaining clean-probe budget for one watch tick.
        Bounded (at most the probes still owed), back-off-spaced, and
        deadline-checked — the shape SWL701 (retry-discipline) demands
        of every marked retry loop."""
        deadline = time.monotonic() + self.probe_timeout_s
        attempt = 0
        while h.clean_probes < self.probe_clean_n:
            if attempt >= self.probe_clean_n:  # bound per tick
                return False
            if time.monotonic() >= deadline:  # deadline check
                h.clean_probes = 0
                return False
            if not self._probe_once(eng):
                h.clean_probes = 0
                return False
            h.clean_probes += 1
            attempt += 1
            time.sleep(self.poll_s * (attempt + 1))  # backoff spacing
        return True

    def _probe_once(self, eng: Engine) -> bool:
        done = threading.Event()
        result: Dict[str, Any] = {}

        def on_done(rid, toks, reason):
            result["reason"] = reason
            done.set()

        try:
            from .sampling import SamplingParams

            eng.submit(GenRequest(
                prompt=[1, 2, 3],
                sampling=SamplingParams(max_new_tokens=1, temperature=0.0),
                priority=3, on_done=on_done,
                metadata={"probe": True}))
        except Exception:
            logger.exception("lane probe submit failed")
            return False
        if not done.wait(self.probe_timeout_s):
            return False
        return result.get("reason") in ("length", "eos")

    # ---------------------------------------------------------- watchdogs

    def _sweep_deadlines(self) -> None:
        """Requests past their deadline fail NOW with the final reason
        "deadline" — whether queued, decoding, or parked on a retry
        timer (which no engine's own sweep can see)."""
        now = time.time()
        with self._lock:
            expired = [tr for tr in self._tracked.values()
                       if tr.deadline is not None and now > tr.deadline]
        for tr in expired:
            with tr.lock:
                if tr.done:
                    continue
                tr.attempt += 1  # stale-proof in-flight callbacks
                lane = tr.lane
            try:
                self.lanes[lane].cancel(tr.request.request_id)
            except Exception:
                logger.exception("deadline cancel failed")
            self.metrics.counters["requests_deadline_expired"].inc()
            self._finalize(tr, "deadline")

    def _detect_retry_storm(self) -> None:
        """Flag a retry storm (a flapping lane re-failing its migrated
        requests) as a flight instant so the post-mortem ring names the
        moment, and keep the sentinel's retry_rate SLO honest."""
        cur = self.metrics.counters["requests_retried"].value
        delta, self._prev_retried = cur - self._prev_retried, cur
        if delta >= self.storm_n and not self._storming:
            self._storming = True
            self.flight.record_event(
                {"kind": "retry.storm", "retries_in_window": delta,
                 "window_s": round(self.poll_s, 3)})
            TRACER.instant("retry.storm", cat="supervisor",
                           args={"retries": delta})
        elif delta == 0:
            self._storming = False

    # -------------------------------------------------------------- status

    def status(self) -> Dict[str, Any]:
        with self._lock:
            tracked = len(self._tracked)
        c = self.metrics.counters
        return {
            "lanes": [
                {"lane": i,
                 "state": h.state.name.lower(),
                 "state_code": int(h.state),
                 "beat_age_s": round(eng.beat_age_s(), 4),
                 "thread_alive": eng.alive(),
                 "quarantines": h.quarantines,
                 "restarts": h.restarts}
                for i, (eng, h) in enumerate(zip(self.lanes, self.health))
            ],
            "tracked_requests": tracked,
            "requests_migrated": c["requests_migrated"].value,
            "requests_retried": c["requests_retried"].value,
            "requests_shed": c["requests_shed"].value,
            "requests_deadline_expired":
                c["requests_deadline_expired"].value,
            "lane_quarantines": c["lane_quarantines"].value,
            "lane_readmissions": c["lane_readmissions"].value,
            "config": {
                "suspect_s": self.suspect_s,
                "quarantine_s": self.quarantine_s,
                "probe_clean_n": self.probe_clean_n,
                "deadline_s": self.deadline_s,
                "retries": self.retries,
            },
        }

    def prometheus_lines(self) -> List[str]:
        """``swarmdb_lane_state`` gauges for /metrics (0=alive,
        1=suspect, 2=quarantined — same stable-code convention as the
        HA role gauge). The migration/shed/retry counters ride the
        shared registry and are exported with every other counter."""
        lines = ["# TYPE swarmdb_lane_state gauge"]
        for i, h in enumerate(self.health):
            lines.append(f'swarmdb_lane_state{{lane="{i}"}} '
                         f"{int(h.state)}")
        lines.append("# TYPE swarmdb_lane_beat_age_seconds gauge")
        for i, eng in enumerate(self.lanes):
            lines.append(f'swarmdb_lane_beat_age_seconds{{lane="{i}"}} '
                         f"{round(eng.beat_age_s(), 4)}")
        return lines
