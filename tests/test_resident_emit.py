"""The resident loop waits for a vote, not for the host's work (ISSUE 40):
one packed operand a chunk, a vote reckoned from the buffer and the
session's snapshot, and the engine thread emitting each block a chunk
behind the device. CPU, tiny dense and tiny routed engines."""

import os
import re
import threading
import time

import numpy as np
import pytest

from swarmdb_tpu.backend import engine as engine_mod
from swarmdb_tpu.backend.engine import (RESIDENT_PROGRAM_NAMES, GenRequest,
                                        _pack_resident_block,
                                        _ResidentBlock, _ResidentSession,
                                        _unpack_resident_block)
from swarmdb_tpu.backend.sampling import SamplingParams
from swarmdb_tpu.backend.service import build_backend_engine
from swarmdb_tpu.models.configs import TINY_DEBUG, TINY_MOE
from swarmdb_tpu.obs import TRACER

PS, K, B, MAX_SEQ = 8, 4, 4, 128
CONFIGS = {"dense": TINY_DEBUG, "routed": TINY_MOE}


def _build(kind, scan=False, **kw):
    was = os.environ.get("SWARMDB_EMIT_RING")
    if scan:
        os.environ["SWARMDB_EMIT_RING"] = "0"
    try:
        kw.setdefault("max_batch", B)
        eng, _tok = build_backend_engine(
            CONFIGS[kind], max_seq=MAX_SEQ, paged=True, page_size=PS,
            decode_chunk=K, **kw)
    finally:
        if scan:
            if was is None:
                os.environ.pop("SWARMDB_EMIT_RING")
            else:
                os.environ["SWARMDB_EMIT_RING"] = was
    assert eng._use_resident() == (not scan)
    return eng


def _submit(eng, prompt, max_new, on_token=None):
    seen, done = {"stream": []}, threading.Event()
    req = GenRequest(prompt=list(prompt),
                     sampling=SamplingParams(max_new_tokens=max_new))

    def _on_token(rid, tok):
        seen["stream"].append(tok)
        if on_token is not None:
            on_token(rid, tok)

    def _on_done(_rid, toks, reason):
        # on_token precedes on_done: the stream is whole when this fires
        seen.update(tokens=list(toks), reason=reason,
                    streamed=list(seen["stream"]),
                    logprobs=list(req.metadata["logprobs"]),
                    routing=req.routing, complete=req.routing_complete)
        done.set()

    req.on_token, req.on_done = _on_token, _on_done
    seen["rid"] = eng.submit(req)
    return done, seen


def _traffic(eng):
    """Six requests of other lengths over four slots, so sessions end on
    arrivals, by length and (where the model samples one) by EOS."""
    rng = np.random.default_rng(40)
    vocab = CONFIGS["dense"].vocab_size
    prompts = [rng.integers(3, vocab, size=n).tolist()
               for n in (19, 37, 8, 5, 26, 11)]
    budgets = (11, 6, 14, 1, 9, 23)
    pending = [_submit(eng, p, m) for p, m in zip(prompts, budgets)]
    out = []
    for done, seen in pending:
        assert done.wait(180)
        out.append(seen)
    return out


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


def _dispatch_as_a_chip_does(eng):
    """The CPU backend runs a program that has a host callback on the
    calling thread, so there the engine thread is never back in time to
    take a block. A chip's dispatch returns at once: here each resident
    program (the real one, with its real callback) runs on a thread of
    the test's that stands in for the device, and the call hands back
    the chunk counter as a pending value."""
    def asynchronously(fn):
        def dispatch(*args):
            n = _Pending()

            def device():
                try:
                    n_dev, lt, llp, cache = fn(*args)
                    eng._last_tokens, eng._last_lps, eng.cache = (
                        lt, llp, cache)
                    n.value = int(n_dev)
                finally:
                    n.over.set()

            threading.Thread(target=device, daemon=True).start()
            return n, eng._last_tokens, eng._last_lps, eng.cache
        return dispatch

    eng._resident_variants = tuple(
        asynchronously(fn) for fn in eng._resident_variants)


# ------------------------------------------------ the same work, handed on


DISPATCH = pytest.mark.parametrize("chip", [True, False],
                                   ids=["returns_at_once", "returns_late"])


@DISPATCH
@pytest.mark.parametrize("kind", ["dense", "routed"])
def test_same_stream_as_the_scan_path_and_none_of_it_on_the_callback(
        kind, chip):
    """Tokens, log-probs, finish reasons and routing records are the scan
    path's; both passes over a block (``_settle_token``,
    ``_settle_retire``, ``_deliver_emit``, ``_deliver_retired``) run on the
    engine's loop thread, never on the callback's; the device is never more than one
    chunk ahead of the emission; after every ``_run_resident`` the FIFO
    is empty and every live slot's dispatched extent is its confirmed
    one; ``engine.emit`` says how far behind the callback it began.
    Where the dispatch returns only when the program is over (the CPU
    backend as it is), the stream is the same and nothing waits on the
    FIFO: the callback had nobody to hand the blocks to."""
    eng = _build(kind)
    if chip:
        _dispatch_as_a_chip_does(eng)
    threads = {"emit": set(), "callback": set()}
    after_session, waiting = [], []
    process = eng._process_host_block

    def process_spy(*a, **kw):
        waiting.append(eng._resident_fifo.qsize())
        return process(*a, **kw)

    eng._process_host_block = process_spy
    for name in ("_settle_token", "_settle_retire", "_deliver_emit",
                 "_deliver_retired"):
        def spy(*a, _fn=getattr(eng, name), **kw):
            threads["emit"].add(threading.get_ident())
            return _fn(*a, **kw)
        setattr(eng, name, spy)
    callback = eng._resident_emit

    def callback_spy(packed):
        threads["callback"].add(threading.get_ident())
        return callback(packed)

    eng._resident_emit = callback_spy    # traced under the spy
    run_resident = eng._run_resident

    def run_spy():
        run_resident()
        after_session.append(
            eng._resident_fifo.empty() and eng._resident is None
            and all(s.dispatched_position == s.position
                    for s in eng.slots if s.active))

    eng._run_resident = run_spy
    TRACER.reset()
    was = TRACER.enabled
    TRACER.set_enabled(True)
    eng.start()
    try:
        got = _traffic(eng)
        loop_thread = eng._thread.ident
    finally:
        eng.stop()
        TRACER.set_enabled(was)
    emits = [e for e in TRACER.snapshot() if e["name"] == "engine.emit"]
    scan = _build(kind, scan=True)
    scan.start()
    try:
        want = _traffic(scan)
    finally:
        scan.stop()
    for g, w in zip(got, want):
        assert g["tokens"] == w["tokens"] and g["reason"] == w["reason"]
        assert g["streamed"] == g["tokens"]
        np.testing.assert_allclose(g["logprobs"], w["logprobs"],
                                   rtol=1e-5, atol=1e-6)
        if kind == "routed":
            assert g["complete"] and w["complete"]
            np.testing.assert_array_equal(g["routing"], w["routing"])
        else:
            assert g["routing"] is None
    assert {g["reason"] for g in got} <= {"length", "eos"}
    if chip:
        assert threads["emit"] == {loop_thread}
        assert (threads["callback"]
                and loop_thread not in threads["callback"])
    assert after_session and all(after_session)
    assert waiting and max(waiting) <= (1 if chip else 0)
    c = eng.metrics.counters
    assert c["engine_resident_chunks"].value >= len(after_session)
    assert c["resident_votes_stale"].value == 0    # nothing was cancelled
    assert c["routing_incomplete_requests"].value == 0
    assert len(emits) == c["engine_resident_chunks"].value
    assert all(e["args"]["behind_us"] >= 0 for e in emits)


# --------------------------------------------------------------- the vote


@pytest.fixture(scope="module")
def idle_engine():
    """A dense engine that never starts: its vote and its consumer are
    driven by hand."""
    return _build("dense")


def _occupy(eng, lanes, left=100, first=False, pos0=16):
    """Slots ``lanes`` hold a live request each, as admission leaves
    them; returns the session ``_run_resident`` would build."""
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
            at = pos0 if np.isscalar(pos0) else pos0[i]
            s.request = GenRequest(
                prompt=[5] * at, sampling=SamplingParams(
                    max_new_tokens=left if np.isscalar(left) else left[i]))
            s.generated, s.logprobs = [], []
            s.pending_token = bool(first)
            s.position = s.dispatched_position = at
            s.first_token_at = s.admitted_at = time.time()
            s.routing = None
            snap.append((i, s.request, at))
            pos[i], live[i] = at, True
            lft[i] = s.request.sampling.max_new_tokens
            fst[i] = bool(first)
    ses = _ResidentSession(snap, pos, lft, fst, live.copy(), 50)
    ses.consuming = True        # the test plays the engine thread
    return ses


def _block(eos_at=None, eos_id=2):
    blk = np.full((K + 1, B), 7, np.int32)
    if eos_at is not None:
        blk[eos_at] = eos_id
    return blk


class _Armed:
    def pending(self):
        return True


VOTES = {
    # name: (live lanes, queued, what is done to the set-up, vote)
    "queue_and_a_slot_free_now": ({0, 1, 2}, 1, {}, False),
    "queue_and_freed_by_eos": ({0, 1, 2, 3}, 1, {"eos_at": (2, 1)}, False),
    "queue_and_freed_by_length": ({0, 1, 2, 3}, 1,
                                  {"left": [100, 100, K, 100]}, False),
    "queue_and_freed_by_first_token": (
        {0, 1, 2, 3}, 1, {"left": [100, K + 1, 100, 100], "first": True},
        False),
    "queue_and_freed_at_max_seq": ({0, 1, 2, 3}, 1,
                                   {"pos0": [16, MAX_SEQ - K + 1, 16, 16]},
                                   False),
    "queue_and_nothing_freed": ({0, 1, 2, 3}, 1,
                                {"left": [100, 100, K + 1, 100]}, True),
    "no_queue_and_one_lane_retires": ({0, 1}, 0, {"eos_at": (3, 0)}, True),
    "stopping": ({0, 1, 2, 3}, 0, {"stop": True}, False),
    "chaos_pending": ({0, 1, 2, 3}, 0, {"chaos": True}, False),
    "every_lane_done": ({0, 1}, 0, {"left": [K, 2, 0, 0]}, False),
    "queue_and_a_cancel_flagged": ({0, 1, 2, 3}, 1, {"cancel": 3}, False),
    "a_block_failed": ({0, 1, 2, 3}, 0, {"failed": True}, False),
}


@pytest.mark.parametrize("case", sorted(VOTES))
def test_the_vote_reckons_what_processing_will_find(idle_engine, case,
                                                    monkeypatch):
    """The vote is taken before the block is processed; it must say what
    the old rule said after: and the lanes it expects live afterwards are
    the lanes ``_process_host_block`` then leaves active."""
    eng = idle_engine
    lanes, queued, how, want = VOTES[case]
    ses = _occupy(eng, lanes, left=how.get("left", 100),
                  first=how.get("first", False), pos0=how.get("pos0", 16))
    block = _block(how.get("eos_at"), eng.eos_id)
    monkeypatch.setattr(eng, "_queue", [object()] * queued)
    monkeypatch.setattr(eng, "_stop", bool(how.get("stop")))
    monkeypatch.setattr(eng, "chaos_step",
                        _Armed() if how.get("chaos") else None)
    if "cancel" in how:
        eng.slots[how["cancel"]].cancelled = True
    ses.failed = bool(how.get("failed"))
    vote, saw = eng._resident_vote(ses, block, 0)
    assert vote is want
    if how.get("stop") or how.get("chaos") or how.get("failed"):
        return
    stale = eng.metrics.counters["resident_votes_stale"].value
    lps = np.zeros((K + 1, B), np.float32)
    last = eng._resident_block(ses, _ResidentBlock(
        block, lps, None, 0, time.monotonic_ns(), vote, saw, not vote))
    assert last is (not vote)
    assert [s.active for s in eng.slots] == ses.alive.tolist()
    assert eng.metrics.counters["resident_votes_stale"].value == stale


def test_the_vote_stays_exact_over_a_session(idle_engine, monkeypatch):
    """Chunk after chunk, random EOS columns and budgets: after each
    block the slots still active are the lanes the vote expected."""
    eng = idle_engine
    rng = np.random.default_rng(4040)
    monkeypatch.setattr(eng, "_queue", [])
    for trial in range(20):
        left = rng.integers(1, 5 * K, size=B).tolist()
        first = bool(trial % 2)
        ses = _occupy(eng, {0, 1, 2, 3}, left=left, first=first,
                      pos0=int(rng.integers(8, MAX_SEQ - 2 * K)))
        for n in range(6):
            block = rng.integers(3, 60, size=(K + 1, B)).astype(np.int32)
            if rng.random() < 0.4:
                block[rng.integers(0, K + 1), rng.integers(0, B)] = eng.eos_id
            if n or not first:
                block[0] = 7       # a fed token is no EOS (it was emitted)
            vote, saw = eng._resident_vote(ses, block, n)
            eng._resident_block(ses, _ResidentBlock(
                block, np.zeros((K + 1, B), np.float32), None, n,
                time.monotonic_ns(), vote, saw, not vote))
            assert [s.active for s in eng.slots] == ses.alive.tolist()
            assert vote is bool(ses.alive.any())
            if not vote:
                break
    assert eng.metrics.counters["resident_votes_stale"].value == 0


def test_a_cancel_is_honoured_a_chunk_late_at_most_and_counted(
        idle_engine, monkeypatch):
    """Another thread flags a cancel after the vote on block 0 and before
    the block is processed: the vote had said continue, processing frees
    the slot with work queued (counted stale), and the next vote stops."""
    eng = idle_engine
    ses = _occupy(eng, {0, 1, 2, 3})
    monkeypatch.setattr(eng, "_queue", [object()])
    monkeypatch.setattr(eng, "_resident", ses)
    packed = np.array(_pack_resident_block(
        _block(), np.zeros((K + 1, B), np.float32), np.int32(0),
        np.zeros(B, bool)))
    stale = eng.metrics.counters["resident_votes_stale"].value
    assert bool(eng._resident_emit(packed)) is True
    done = []
    eng.slots[2].request.on_done = lambda _r, _t, why: done.append(why)
    eng.slots[2].cancelled = True              # the other thread
    blk = eng._resident_fifo.get_nowait()
    assert (blk.n, blk.vote, blk.queued, blk.last) == (0, True, 1, False)
    assert eng._resident_block(ses, blk) is False
    assert done == ["cancelled"] and not eng.slots[2].active
    assert eng.metrics.counters["resident_votes_stale"].value == stale + 1
    packed[2 * (K + 1) * B] = 1                # the next chunk
    assert bool(eng._resident_emit(packed)) is False
    blk = eng._resident_fifo.get_nowait()
    assert (blk.n, blk.vote, blk.last) == (1, False, True)
    assert eng._resident_block(ses, blk) is True
    assert eng.metrics.counters["resident_votes_stale"].value == stale + 1
    assert eng._resident_fifo.empty()


@DISPATCH
def test_a_cancel_from_the_stream_path_ends_the_session_for_the_queue(chip):
    """A running engine, two slots, three requests: whichever of the two
    live requests streams its sixth token first cancels the other (a stop
    sequence's path: ``on_token`` -> ``cancel``) while the third waits.
    ``on_token`` runs in a block's second pass, after every slot has
    settled, so the flag is honoured at the next block (ISSUE 45); the
    vote on that block is taken after this one was delivered and reads
    the flag, so the session ends there, nothing went stale, and the
    third is served. The cancelled stream holds no more than the block
    it was flagged in and the one that retired it."""
    eng = _build("dense", max_batch=2)
    if chip:
        _dispatch_as_a_chip_does(eng)
    rids, cancelled = {}, []

    def stop_the_other(rid, _tok):
        mine = "a" if rids.get("a") == rid else "b"
        seen = (a if mine == "a" else b)[1]
        if len(seen["stream"]) == 6 and not cancelled:
            cancelled.append(rids["b" if mine == "a" else "a"])
            assert eng.cancel(cancelled[0])

    eng.start()
    try:
        a = _submit(eng, [3, 4, 5] * 4, 40, on_token=stop_the_other)
        rids["a"] = a[1]["rid"]
        b = _submit(eng, [6, 7, 8] * 5, 40, on_token=stop_the_other)
        rids["b"] = b[1]["rid"]
        c = _submit(eng, [9, 10] * 6, 7)
        for done, _seen in (a, b, c):
            assert done.wait(180)
    finally:
        eng.stop()
    assert sorted((a[1]["reason"], b[1]["reason"])) == ["cancelled",
                                                        "length"]
    assert c[1]["reason"] in ("length", "eos") and c[1]["tokens"]
    counters = eng.metrics.counters
    assert counters["resident_votes_stale"].value == 0
    assert counters["engine_cancelled"].value == 1
    gone = a[1] if a[1]["reason"] == "cancelled" else b[1]
    assert len(gone["stream"]) <= 6 + 2 * K


def test_last_mirrors_the_loops_cond(idle_engine, monkeypatch):
    """``last`` is ``not cond``: the chunk bound, the vote, ``done``."""
    eng = idle_engine
    monkeypatch.setattr(eng, "_queue", [])
    lps = np.zeros((K + 1, B), np.float32)
    for n, max_chunks, done, want in (
            (0, 3, [0, 0, 1, 1], False), (1, 3, [0, 1, 1, 1], False),
            (2, 3, [0, 0, 1, 1], True), (0, 3, [1, 1, 1, 1], True)):
        ses = _occupy(eng, {0, 1})
        ses.max_chunks = max_chunks
        monkeypatch.setattr(eng, "_resident", ses)
        eng._resident_emit(np.asarray(_pack_resident_block(
            _block(), lps, np.int32(n), np.asarray(done, bool))))
        blk = eng._resident_fifo.get_nowait()
        assert blk.vote is True and blk.last is want
    monkeypatch.setattr(eng, "_resident", None)   # between sessions
    assert bool(eng._resident_emit(np.zeros(3, np.int32))) is False
    assert eng._resident_fifo.empty()


@pytest.mark.parametrize("routed", [None, (2, 2, 4), (3, 1, 8)])
def test_the_packed_block_round_trips(routed):
    """Dense: no routing part. Routed: int16 two to a word, an odd count
    (3 steps x 3 lanes x 3 layers x 1) padded by one."""
    rng = np.random.default_rng(7)
    k1, b = 4, 3
    toks = rng.integers(0, 1 << 20, size=(k1, b)).astype(np.int32)
    lps = rng.standard_normal((k1, b)).astype(np.float32)
    done = np.asarray([True, False, True])
    args = [toks, lps, np.int32(6), done]
    if routed:
        routing = rng.integers(-9, 9, size=(k1 - 1, b, *routed[:2])).astype(
            np.int16)
        args.append(routing)
    buf = np.asarray(_pack_resident_block(*map(np.asarray, args)))
    assert buf.dtype == np.int32 and buf.ndim == 1
    block, got_lps, n, got_done, got_routing = _unpack_resident_block(
        buf, k1, b, routed)
    np.testing.assert_array_equal(block, toks)
    np.testing.assert_array_equal(got_lps, lps)
    assert n == 6 and got_done.tolist() == done.tolist()
    words = 2 * k1 * b + 1 + b
    if routed:
        np.testing.assert_array_equal(got_routing, routing)
        words += (routing.size + 1) // 2
    else:
        assert got_routing is None
    assert buf.size == words


# ------------------------------------------------- when something goes wrong


@DISPATCH
def test_a_block_that_fails_ends_the_session_and_the_engine_serves_on(chip):
    eng = _build("dense", max_batch=2)
    if chip:
        _dispatch_as_a_chip_does(eng)
    process = eng._process_host_block
    calls = []

    def failing(*a, **kw):
        calls.append(kw.get("stamp_ns", 0))
        if len(calls) == 1:
            raise RuntimeError("planted")
        return process(*a, **kw)

    eng.start()
    try:
        prompt = [4, 5, 6] * 5
        done, clean = _submit(eng, prompt, 9)
        assert done.wait(180)
        sessions = eng.metrics.counters["engine_resident_sessions"].value
        eng._process_host_block = failing
        done, hit = _submit(eng, [9, 8, 7] * 4, 3 * K)
        assert done.wait(180)       # the planted failure loses a block,
        assert hit["reason"] in ("length", "eos")    # not the engine
        # the failed block's session stopped at the next vote: the
        # request needed more sessions than the one it would have had
        # (on_done fires inside the last one, before it is counted)
        deadline = time.time() + 10
        counted = eng.metrics.counters["engine_resident_sessions"]
        while counted.value < sessions + 2 and time.time() < deadline:
            time.sleep(0.02)
        assert counted.value >= sessions + 2
        done, again = _submit(eng, prompt, 9)
        assert done.wait(180)
        assert again["tokens"] == clean["tokens"]
        assert eng._resident_fifo.empty() and eng.alive()
    finally:
        eng.stop()


@pytest.mark.parametrize("ready_after", [0, 2])
def test_the_fifo_times_out_when_the_program_never_calls_back(
        idle_engine, monkeypatch, ready_after):
    """No block ever comes (the device program failed): the consumer asks
    the device, and returns once the program is over."""
    eng = idle_engine
    monkeypatch.setattr(engine_mod, "_RESIDENT_POLL_S", 0.01)

    class NeverCalledBack:
        asked = 0

        def is_ready(self):
            self.asked += 1
            return self.asked > ready_after

    n_dev = NeverCalledBack()
    t = time.monotonic()
    eng._resident_consume(_occupy(eng, {0}), n_dev)
    assert n_dev.asked == ready_after + 1
    assert time.monotonic() - t < 5.0
    # and what a failed program left behind is thrown away
    eng._resident_fifo.put(None)
    eng._resident_flush(None)
    assert eng._resident_fifo.empty()


# ------------------------------------------------------------ compile time


@pytest.fixture(scope="module", params=["dense", "routed"])
def lowered(request):
    eng = _build(request.param)
    texts = {}
    for fn, specs in eng.warmup_call_plan():
        name = getattr(fn, "__name__", "")
        if name in RESIDENT_PROGRAM_NAMES:
            texts[name] = fn.lower(*specs).as_text()
    return eng, texts


@pytest.mark.parametrize("name", RESIDENT_PROGRAM_NAMES)
def test_a_resident_program_has_one_callback_with_one_operand(lowered,
                                                              name):
    eng, texts = lowered
    calls = [ln for ln in texts[name].splitlines()
             if "custom_call" in ln and "callback" in ln]
    assert len(calls) == 1, calls
    operands = re.search(r":\s*\((.*?)\)\s*->", calls[0]).group(1)
    arrays = [t for t in operands.split(", ") if t.startswith("tensor<")]
    words = 2 * (K + 1) * B + 1 + B
    if eng._routed is not None:
        l_routed, k, _e = eng._routed
        words += (K * B * l_routed * k + 1) // 2
    assert arrays == [f"tensor<{words}xi32>"]
