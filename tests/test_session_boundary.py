"""At a session boundary the device waits for the wave's dispatch and for
nothing else (ISSUE 45): admission's plan is made between a resident
session's blocks (``_plan_ahead``, ``_held_plan``), and the block that
ends a session for work to admit is settled before the round's wave and
delivered behind it (``_settle_block``, ``_deliver_block``). CPU, a tiny
dense paged engine. The test plays the device (a scripted program whose
dispatch returns at once, as a chip's does, and whose blocks the test
writes) or the engine thread (an engine that never starts), as
``tests/test_resident_emit.py`` does."""

import threading
import time

import numpy as np
import pytest

from swarmdb_tpu.backend.engine import (GenRequest, _pack_resident_block,
                                        _ResidentBlock, _ResidentSession)
from swarmdb_tpu.backend.sampling import SamplingParams
from swarmdb_tpu.backend.service import build_backend_engine
from swarmdb_tpu.models.configs import TINY_DEBUG
from swarmdb_tpu.obs import TRACER

PS, K, B, MAX_SEQ = 8, 4, 4, 128
EOS = TINY_DEBUG.vocab_size - 1     # set on the engine below: a token the
FILL = 7                            # scripted blocks may or may not hold


def _build(**kw):
    kw.setdefault("max_batch", B)
    eng, _tok = build_backend_engine(
        TINY_DEBUG, max_seq=MAX_SEQ, paged=True, page_size=PS,
        decode_chunk=K, **kw)
    assert eng._use_resident()
    return eng


def _request(log, name, prompt, max_new, **kw):
    req = GenRequest(prompt=list(prompt), sampling=SamplingParams(
        max_new_tokens=max_new), **kw)
    req.done = threading.Event()

    def on_token(_rid, tok):
        log.append(("token", name, tok))

    def on_done(_rid, toks, reason):
        log.append(("done", name, reason, tuple(toks)))
        req.done.set()

    req.on_token, req.on_done = on_token, on_done
    return req


def _prompt(n, salt):
    return [3 + (salt * 17 + j * 5) % 50 for j in range(n)]


# ------------------------------------------------ the test plays the device


class _Pending:
    """The chunk counter of a program that is still running."""

    def __init__(self):
        self.over, self.value = threading.Event(), 0

    def is_ready(self):
        return self.over.is_set()

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        self.over.wait()
        return np.asarray(self.value, np.int32)


class _ScriptedDevice:
    """Stands in for the resident programs: the dispatch returns at once,
    and a thread of the test's calls the engine's real callback with one
    block a chunk until the vote says stop. ``script(session, chunk)``
    gives the block's ``[K + 1, batch]`` tokens. A chunk "runs" until the
    test lets it end (``step``), or for no time at all (``free_run``)."""

    def __init__(self, eng, script):
        self.eng, self.script = eng, script
        self.sessions = 0
        self.permits = threading.Semaphore(0)
        self.free = threading.Event()
        self.running = threading.Event()   # a chunk is "on the device"
        eng._resident_variants = (self.dispatch,) * 3

    def step(self):
        self.permits.release()

    def free_run(self):
        self.free.set()
        self.permits.release()

    def dispatch(self, _params, lt, llp, _positions, cache, _keys, _temp,
                 _topk, _topp, _stop_pos, _live, max_chunks):
        n = _Pending()
        session, self.sessions = self.sessions, self.sessions + 1
        batch = self.eng.max_batch

        def device():
            try:
                for chunk in range(int(max_chunks)):
                    self.running.set()
                    while not self.free.is_set():
                        if self.permits.acquire(timeout=0.05):
                            break
                    toks = np.asarray(self.script(session, chunk), np.int32)
                    packed = _pack_resident_block(
                        toks, np.zeros((K + 1, batch), np.float32),
                        np.int32(chunk), np.zeros(batch, bool))
                    self.running.clear()
                    vote = bool(self.eng._resident_emit(np.asarray(packed)))
                    n.value = chunk + 1
                    if not vote:
                        break
            finally:
                n.over.set()

        threading.Thread(target=device, daemon=True).start()
        return n, lt, llp, cache


def _wait(cond, what, timeout=60.0):
    t = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < t, what
        time.sleep(0.002)


NB = 6      # slots of the boundary run: one stays free for the late request


def _boundary_run(deferred):
    """A request decodes alone (slot 0); three arrive, end its session and
    are admitted by a wave it rides. The next session's first block then
    holds the rider's fed token first (slot 0), an EOS (slot 1), a
    request's last token (slot 2) and the end of the cache lane (slot 3),
    and while that chunk runs a fifth request arrives (slot 4 is free).
    ``deferred`` False restores the order before ISSUE 45: nothing planned
    ahead, every block delivered where it is settled."""
    eng = _build(max_batch=NB)
    eng.eos_id = EOS
    log, marks = [], {}

    def script(session, chunk):
        blk = np.full((K + 1, NB), FILL, np.int32)
        if session == 1 and chunk == 0:
            blk[:, 0] = [40, 41, 42, 43, 44]   # row 0: what slot 0 rode for
            blk[2, 1] = EOS                    # slot 1: two tokens, then EOS
        return blk

    dev = _ScriptedDevice(eng, script)
    if not deferred:
        eng._plan_ahead = lambda: None
        process = eng._process_host_block
        eng._process_host_block = lambda *a, **kw: process(
            *a, **{**kw, "defer": False})
    mirrored = eng._mirrored

    def mirrored_spy(call_id, *args):
        if call_id == eng.CALL_PAGED_PREFILL_RAGGED:
            log.append(("wave", eng._wave_n + 1))
        return mirrored(call_id, *args)

    eng._mirrored = mirrored_spy
    plan_ahead = eng._plan_ahead

    def plan_spy():
        plan_ahead()
        held = eng._held_plan
        if held is not None and held.popped:
            marks["held"] = [r.request_id for r in held.popped]
            marks["queue_after_plan"] = len(eng._queue)
            marks["planned_at"] = time.time()

    eng._plan_ahead = plan_spy
    rider = _request(log, "rider", _prompt(9, 1), 40)
    three = [_request(log, "eos", _prompt(11, 2), 40),
             _request(log, "length", _prompt(6, 3), 1 + K),
             _request(log, "max_seq", _prompt(MAX_SEQ - 3, 4), 40)]
    late = _request(log, "late", _prompt(13, 5), 3)
    TRACER.reset()
    was = TRACER.enabled
    TRACER.set_enabled(True)
    eng.start()
    try:
        eng.submit(rider)
        _wait(lambda: dev.sessions == 1 and dev.running.is_set(),
              "session 0 never began")
        for r in three:
            eng.submit(r)
        if deferred:
            _wait(lambda: len(marks.get("held", ())) == 3,
                  "the three were not planned ahead")
        dev.step()
        _wait(lambda: dev.sessions == 2 and dev.running.is_set(),
              "session 1 never began")
        marks["slots"] = [s.request for s in eng.slots]
        t_submit = time.time()
        eng.submit(late)
        if deferred:
            _wait(lambda: marks.get("held") == [late.request_id],
                  "the late one was not planned ahead")
        time.sleep(0.15)        # the wait that queue_wait_s has to show
        marks["released_at"] = time.time()
        marks["late_waited"] = marks["released_at"] - t_submit
        dev.free_run()
        for r in (rider, *three, late):
            assert r.done.wait(60), r
    finally:
        eng.stop()
        TRACER.set_enabled(was)
    assert marks["slots"] == [rider, *three, None, None]
    return eng, log, marks, [e for e in TRACER.snapshot()
                             if e["cat"] == "engine"]


@pytest.fixture(scope="module")
def both_orders():
    return _boundary_run(deferred=True), _boundary_run(deferred=False)


def _by_request(log):
    out = {}
    for ev in log:
        if ev[0] != "wave":
            out.setdefault(ev[1], []).append(ev)
    return out


def test_streams_callbacks_and_reasons_are_the_back_to_back_orders(
        both_orders):
    """(a) The same tokens to the same requests in the same order, the
    same ``on_done`` after each request's last token with the same reason,
    and the requests finish in the same order, whichever way the boundary
    blocks went."""
    (_e1, new, _m1, _s1), (_e2, old, _m2, _s2) = both_orders
    assert _by_request(new) == _by_request(old)
    assert ([ev[1] for ev in new if ev[0] == "done"]
            == [ev[1] for ev in old if ev[0] == "done"])
    got = _by_request(new)
    assert got["eos"][-1] == ("done", "eos", "eos", (FILL, FILL))
    assert got["length"][-1] == ("done", "length", "length",
                                 (FILL,) * (1 + K))
    assert got["max_seq"][-1] == ("done", "max_seq", "max_seq", (FILL,) * 4)
    for name, events in got.items():       # on_token, then on_done, once
        assert [ev[0] for ev in events] == (
            ["token"] * (len(events) - 1) + ["done"]), name
        assert tuple(ev[2] for ev in events[:-1]) == events[-1][3], name
    # the rider's fed token leads its block, and nothing is lost round it
    rider = got["rider"][-1][3]
    assert rider[1 + K:2 * (1 + K)] == (40, 41, 42, 43, 44)
    assert got["rider"][-1][2] == "length" and len(rider) == 40
    assert got["late"][-1] == ("done", "late", "length", (FILL,) * 3)


def test_the_boundary_blocks_callbacks_come_behind_the_wave(both_orders):
    """(a) Where the block was deferred, the wave that admits the late
    request is dispatched before the block's first callback; back to back
    it is dispatched after its last. The EOS, the length and the
    ``max_seq`` retirement all lie in that block."""
    (_e1, new, _m1, spans), (_e2, old, _m2, old_spans) = both_orders

    def boundary(log):
        first = next(i for i, ev in enumerate(log)
                     if ev[0] == "token" and ev[2] == 40)
        last = max(i for i, ev in enumerate(log)
                   if ev[0] == "done" and ev[1] in ("eos", "length",
                                                    "max_seq"))
        waves = [i for i, ev in enumerate(log) if ev[0] == "wave"]
        assert len(waves) >= 3 and first < last
        return first, last, waves[-1]

    first, last, wave = boundary(new)
    assert wave < first
    first, last, wave = boundary(old)
    assert last < wave
    behind = [e for e in spans if e["name"] == "engine.emit"
              and e["args"].get("behind_wave")]
    # the rider's lone session ended for the three, the next for the late
    assert len(behind) == 2
    assert all(e["args"]["settle_us"] >= 0 and e["args"]["behind_us"] > 0
               for e in behind)
    assert not [e for e in old_spans if e["name"] == "engine.emit"
                and e["args"].get("behind_wave")]


def test_the_plan_was_made_while_the_chunk_ran_and_stamped_at_dispatch(
        both_orders):
    """(e) The late request's plan is made when it arrives, the session
    still running; ``admitted_at``, ``engine.admit`` and ``queue_wait_s``
    are the wave's: the request waited for the chunk to end, and the
    queue wait says so. The counter and the phases' arguments name what
    was planned ahead."""
    (eng, _log, marks, spans), (old, _l2, _m2, old_spans) = both_orders
    assert marks["queue_after_plan"] == 0      # off the queue, and held
    assert marks["planned_at"] < marks["released_at"]
    admit = [e for e in spans if e["name"] == "engine.admit"]
    assert len(admit) == 5
    assert (max(e["dur_us"] for e in admit) / 1e6
            >= marks["late_waited"] - 0.02)
    assert max(eng._lat_queue_wait._ring) >= marks["late_waited"] - 0.02
    record = {r["rid"]: r for r in eng.flight.requests()}
    assert all(r["admitted_at"] >= marks["released_at"] - 0.02
               for r in record.values() if r["generated"] == 3)
    c = eng.metrics.counters
    assert c["admission_planned_ahead"].value == 4
    assert c["engine_admitted"].value == 5
    assert old.metrics.counters["admission_planned_ahead"].value == 0
    plans = [e for e in spans if e["name"] == "engine.admission.plan"]
    early = [e for e in plans if e["args"].get("early")]
    assert sum(e["args"]["planned"] for e in early) == 4
    assert sum(e["args"].get("ahead", 0) for e in plans
               if not e["args"].get("early")) == 4
    assert not [e for e in old_spans if e["name"] == "engine.admission.plan"
                and e["args"].get("early")]
    # the same rounds either way: as many waves, as many riders
    for name in ("prefill_device_waves", "wave_rider_tokens",
                 "prefill_packed_tokens", "engine_resident_sessions"):
        assert c[name].value == old.metrics.counters[name].value, name
    assert c["wave_rider_tokens"].value == 2


# ------------------------------------------- the test plays the engine thread


@pytest.fixture()
def idle():
    """An engine that never starts; a request was served and retired, so
    the prefix cache holds its prompt's pages."""
    eng = _build()
    eng.eos_id = EOS
    log = []
    warm = _request(log, "warm", _prompt(3 * PS + 2, 9), 4)
    eng.submit(warm)
    eng._admission_round()
    eng._retire(0, "length")
    eng._admission_round()          # the reclaim
    assert not eng._any_active()
    assert eng._prefix.stats()["cached_pages"] >= 3
    return eng, log


def _occupy(eng, lanes, left=100, pending=False, pos0=16):
    """Slots ``lanes`` hold a live request each; returns the session
    ``_run_resident`` would build, with the test as its consumer."""
    snap = []
    pos = np.zeros(B, np.int32)
    lft = np.zeros(B, np.int32)
    fst = np.zeros(B, np.int32)
    live = np.zeros(B, bool)
    for i, s in enumerate(eng.slots):
        s.active = i in lanes
        s.cancelled = False
        s.request = None
        if s.active:
            s.request = GenRequest(prompt=[5] * pos0, sampling=SamplingParams(
                max_new_tokens=left))
            s.generated, s.logprobs = [], []
            s.pending_token = pending
            s.position = s.dispatched_position = pos0
            s.first_token_at = s.admitted_at = time.time()
            s.routing = None
            snap.append((i, s.request, pos0))
            pos[i], live[i], lft[i], fst[i] = pos0, True, left, pending
    ses = _ResidentSession(snap, pos, lft, fst, live.copy(), 50)
    ses.consuming = True
    return ses


def _pool(eng):
    """What a request holds of the pool while it waits: free pages once
    the retired slots are reclaimed, and the cache's pinned pages."""
    alloc = eng.paged.allocator
    alloc.release_taken(alloc.take_pending_frees())
    return alloc.free_count(), eng._prefix.stats()["pinned_pages"]


def test_the_vote_stops_for_a_held_plan_though_the_queue_is_empty(idle):
    """(b) A planned request is off ``_queue``; the vote counts it."""
    eng, log = idle
    ses = _occupy(eng, {1, 2, 3})
    block = np.full((K + 1, B), FILL, np.int32)
    assert eng._resident_vote(ses, block, 0) == (True, 0)
    eng.submit(_request(log, "q", _prompt(3 * PS + 2, 9), 4))
    eng._plan_ahead()
    assert not eng._queue and len(eng._held_plan.popped) == 1
    assert eng._held_plan.rows[0][0] == 0           # the slot free now
    assert eng.stats()["queued"] == 1
    assert eng._resident_vote(ses, block, 1) == (False, 1)


@pytest.mark.parametrize("short_of", ["slots", "pages", "the_round",
                                      "a_deadline", "a_closed_gate"])
def test_short_of_anything_nothing_is_planned_and_priority_decides(
        idle, short_of):
    """(c) Where the boundary's round might not admit every queued
    request the queue is left alone, and the round admits in priority
    order into what is free then."""
    eng, log = idle
    alloc = eng.paged.allocator
    low = _request(log, "low", _prompt(20, 1), 4, priority=0)
    high = _request(log, "high", _prompt(21, 2), 4, priority=2)
    if short_of == "slots":
        ses = _occupy(eng, {0, 1, 2})               # one slot, two requests
    elif short_of == "pages":
        # one free page and the cache's three: a request's four, not two's
        ses = _occupy(eng, {0})
        eng._bp_high = 1.0           # the gate off: the pool alone decides
        alloc.reserve(alloc.free_count() - 1)
    elif short_of == "the_round":
        ses = _occupy(eng, {0})
        eng.prefill_batch = 1
    elif short_of == "a_deadline":
        ses = _occupy(eng, {0})
        low.deadline = time.time() - 1.0
    else:
        ses = _occupy(eng, {0})
        eng._bp_paused = True
    eng.submit(low)
    eng.submit(high)
    eng._plan_ahead()
    assert eng._held_plan is None and len(eng._queue) == 2
    assert eng._resident_vote(
        ses, np.full((K + 1, B), FILL, np.int32), 0) == (False, 2)
    eng._bp_paused = False
    eng._admission_round()
    admitted = [s.request for s in eng.slots
                if s.active and s.request in (low, high)]
    if short_of == "a_deadline":
        assert admitted == [high] and log[-1][:3] == ("done", "low",
                                                      "deadline")
    elif short_of in ("slots", "pages"):
        assert admitted == [high] and [i[3] for i in eng._queue] == [low]
    else:
        assert admitted == [high, low]      # the lower slot first


HELD_THEN = ["cancel", "deadline", "fail_all", "boundary"]


@pytest.mark.parametrize("then", HELD_THEN)
def test_a_held_plan_gives_back_what_a_queued_request_never_took(idle, then):
    """(d) A request planned ahead holds a slot, pages and pins on its
    cached prefix. Cancelled, past its deadline or failed with the engine
    it leaves the pool as it found it and hears what a queued request
    would; taken up by the boundary's round it is admitted with its hits."""
    eng, log = idle
    ses = _occupy(eng, {1})
    before = _pool(eng)
    req = _request(log, "q", _prompt(3 * PS + 2, 9), 4)
    eng.submit(req)
    eng._plan_ahead()
    held = eng._held_plan
    assert held.popped == [req] and req.request_id in eng._admitting
    free, pinned = _pool(eng)
    assert free < before[0] and pinned == before[1] + 3    # three hit pages
    if then == "cancel":
        assert eng.cancel(req.request_id)
        assert log[-1] == ("done", "q", "cancelled", ())
    elif then == "deadline":
        req.deadline = time.time() - 1.0
        eng._expire_deadlines()
        assert log[-1] == ("done", "q", "deadline", ())
    elif then == "fail_all":
        eng._fail_all("engine_restart")
        assert ("done", "q", "engine_restart", ()) in log
    else:
        eng._admission_round()
        slot = eng.slots[0]
        assert slot.active and slot.request is req
        assert slot.cached_tokens == 3 * PS and eng._held_plan is None
        assert eng.metrics.counters["admission_planned_ahead"].value == 1
        eng._retire(0, "length")
    assert eng._held_plan is None or not eng._held_plan.popped
    assert req.request_id not in eng._admitting and not eng._queue
    if then != "boundary":
        assert eng.cancel(req.request_id) is False      # it is gone
    if then == "fail_all":
        ses = None      # the session's requests were failed with the rest
    assert _pool(eng) == before


def test_stop_puts_a_held_plan_back_on_the_queue():
    """(d) ``stop()`` with a plan held: the loop leaves, the request is
    queued again under the entry it had, and the pool is as before."""
    eng = _build()
    log = []
    dev = _ScriptedDevice(
        eng, lambda *_a: np.full((K + 1, B), FILL, np.int32))
    first = _request(log, "first", _prompt(12, 1), 40)
    queued = _request(log, "queued", _prompt(9, 2), 4, priority=1)
    eng.start()
    try:
        eng.submit(first)
        _wait(lambda: dev.sessions == 1 and dev.running.is_set(),
              "the session never began")
        alloc = eng.paged.allocator
        before = alloc.free_count(), eng._prefix.stats()["pinned_pages"]
        eng.submit(queued)
        _wait(lambda: eng._held_plan is not None
              and eng._held_plan.popped == [queued], "nothing was planned")
        entry = eng._held_plan.entries[0]
        assert alloc.free_count() < before[0]
    finally:
        threading.Timer(0.05, dev.free_run).start()
        eng.stop()
    assert eng._held_plan is None and eng._queue == [entry]
    assert entry[3] is queued and not queued.done.is_set()
    assert queued.request_id not in eng._admitting
    assert _pool(eng) == before
    assert eng._undelivered is None


def test_a_plan_held_when_the_last_row_retires_is_admitted_all_the_same():
    """A request is planned ahead while the only running row decodes its
    last chunk: the session ends with nothing live and nothing on the
    queue, and the loop still has a round to run."""
    eng = _build()
    log = []
    dev = _ScriptedDevice(
        eng, lambda *_a: np.full((K + 1, B), FILL, np.int32))
    first = _request(log, "first", _prompt(12, 1), 1 + K)
    second = _request(log, "second", _prompt(9, 2), 2)
    eng.start()
    try:
        eng.submit(first)
        _wait(lambda: dev.sessions == 1 and dev.running.is_set(),
              "the session never began")
        eng.submit(second)
        _wait(lambda: eng._held_plan is not None
              and eng._held_plan.popped == [second], "nothing was planned")
        assert not eng._queue
        dev.free_run()
        assert first.done.wait(60) and second.done.wait(60)
    finally:
        eng.stop()
    assert [ev for ev in log if ev[0] == "done"] == [
        ("done", "first", "length", (FILL,) * (1 + K)),
        ("done", "second", "length", (FILL,) * 2)]
    assert eng.metrics.counters["admission_planned_ahead"].value == 1


def _mid_session_state():
    eng = _build()
    eng.eos_id = EOS
    log = []
    ses = _occupy(eng, {0, 1, 2, 3}, left=6, pending=True)
    for i, s in enumerate(eng.slots):
        name = f"r{i}"
        s.request.on_token = lambda _r, t, n=name: log.append(("token", n, t))
        s.request.on_done = lambda _r, ts, why, n=name: log.append(
            ("done", n, why, tuple(ts)))
    eng.slots[3].cancelled = True
    for s in eng.slots:
        s.first_token_at = None     # the pending token is each one's first
    block = np.arange(10, 10 + (K + 1) * B, dtype=np.int32).reshape(K + 1, B)
    block[3, 1] = EOS
    return eng, ses, log, block


def test_settle_then_deliver_is_the_single_pass_it_was():
    """(f) On a block that does not end its session the two passes run
    back to back: the callbacks, the slots and the counters are what one
    pass over the block gave, spelled out here; and deferring the same
    block and delivering it later gives the same again."""
    lps = np.zeros((K + 1, B), np.float32)
    outcomes = []
    for defer in (False, True):
        eng, ses, log, block = _mid_session_state()
        blk = _ResidentBlock(block, lps, None, 0, time.monotonic_ns(),
                             not defer, 1 if defer else 0, defer)
        assert eng._resident_block(ses, blk) is defer
        if defer:
            # settled and told to nobody; the slots already say so
            assert log == [] and eng._undelivered is not None
            assert [s.active for s in eng.slots] == [True, False, True,
                                                     False]
            eng._deliver_pending()
        assert eng._undelivered is None
        c = eng.metrics.counters
        outcomes.append((
            list(log), [s.active for s in eng.slots],
            [list(s.generated) for s in eng.slots],
            [s.position for s in eng.slots],
            [s.pending_token for s in eng.slots],
            [s.first_token_at is not None for s in eng.slots],
            {k: c[k].value for k in (
                "tokens_generated", "engine_completed",
                "decode_slot_chunks", "resident_votes_stale")}))
    assert outcomes[0] == outcomes[1]
    log, active, generated, position, pending, first, counters = outcomes[0]
    col = lambda i: [10 + r * B + i for r in range(K + 1)]   # noqa: E731
    assert log == (
        [("token", "r0", t) for t in col(0)]
        + [("token", "r1", t) for t in col(1)[:3]]
        + [("done", "r1", "eos", tuple(col(1)[:3]))]
        + [("token", "r2", t) for t in col(2)]
        + [("done", "r3", "cancelled", ())])
    assert active == [True, False, True, False]
    assert generated[0] == col(0) and generated[2] == col(2)
    assert position[0] == position[2] == 16 + K
    assert pending == [False, False, False, True]    # the cancelled one's
    # the first token's stamp stays with a slot that keeps its request
    assert first == [True, False, True, False]
    assert counters == {"tokens_generated": 13, "engine_completed": 2,
                        "decode_slot_chunks": 4, "resident_votes_stale": 0}
