"""Rolling-KV conversation continuation (paged engine resume path).

A resumed turn — kept pages + suffix-only prefill via
Engine.resume_pages — must generate exactly the tokens a fresh engine
produces when given the full concatenated history as its prompt (the
token stream is identical; only the compute is reused)."""

import numpy as np
import pytest

import jax

from paged_engine import paged_engine
from swarmdb_tpu.backend.engine import Engine, GenRequest
from swarmdb_tpu.backend.sampling import SamplingParams
from swarmdb_tpu.models import llama
from swarmdb_tpu.models.configs import TINY_DEBUG

PS, MAX_SEQ, BATCH = 8, 96, 2


def _mk_engine(params, start=True):
    cfg = TINY_DEBUG
    eng = paged_engine(
        cfg, params, max_batch=BATCH, max_seq=MAX_SEQ, page_size=PS,
        num_pages=1 + 2 * BATCH * (MAX_SEQ // PS), prefix=True, eos_id=-1,
        seed=0, prefill_buckets=[16, 32, 64], decode_chunk=4)
    if start:
        eng.start()
    return eng


@pytest.fixture(scope="module")
def params():
    return llama.init_params(TINY_DEBUG, jax.random.PRNGKey(9))


def _gen_keep(eng, prompt, max_new, resume=None):
    """generate_sync with keep_pages; returns (tokens, pages, written,
    tail)."""
    import threading

    done = threading.Event()
    out = {}

    def on_done(rid, toks, reason):
        out["toks"] = toks
        out["reason"] = reason
        done.set()

    def on_pages(rid, pages, written, tail):
        out["pages"] = pages
        out["written"] = written
        out["tail"] = tail

    req = GenRequest(
        prompt=list(prompt),
        sampling=SamplingParams(max_new_tokens=max_new, temperature=0.0),
        on_done=on_done, on_pages=on_pages, keep_pages=True,
    )
    if resume is not None:
        req.resume_pages = list(resume[0])
        req.resume_len = resume[1]
    eng.submit(req)
    assert done.wait(120)
    assert out["reason"] in ("length", "eos")
    assert "pages" in out, "on_pages never fired"
    return out["toks"], out["pages"], out["written"], out["tail"]


def test_resume_matches_fresh_full_prefill(params):
    rng = np.random.default_rng(3)
    p1 = rng.integers(3, TINY_DEBUG.vocab_size, size=21).tolist()
    new2 = rng.integers(3, TINY_DEBUG.vocab_size, size=9).tolist()
    new3 = rng.integers(3, TINY_DEBUG.vocab_size, size=5).tolist()

    eng = _mk_engine(params)
    try:
        # turn 1 (fresh, keep pages) -> turn 2 (resume) -> turn 3 (resume)
        g1, pages, written, tail = _gen_keep(eng, p1, 7)
        assert written + len(tail) == len(p1) + len(g1)
        assert len(pages) == -(-written // PS)
        g2, pages2, written2, tail2 = _gen_keep(
            eng, tail + new2, 6, resume=(pages, written))
        g3, *_ = _gen_keep(eng, tail2 + new3, 5, resume=(pages2, written2))
    finally:
        eng.stop()

    # reference: fresh engines over the full concatenated streams
    ref = _mk_engine(params)
    try:
        r2, _, _, _ = _gen_keep(ref, p1 + g1 + new2, 6)
    finally:
        ref.stop()
    assert g2 == r2, (g2, r2)

    ref3 = _mk_engine(params)
    try:
        r3, *_ = _gen_keep(ref3, p1 + g1 + new2 + g2 + new3, 5)
    finally:
        ref3.stop()
    assert g3 == r3, (g3, r3)


def test_resume_rejects_bad_requests(params):
    eng = _mk_engine(params)
    try:
        with pytest.raises(ValueError):  # pages don't cover resume_len
            eng.submit(GenRequest(prompt=[1, 2], resume_pages=[1],
                                  resume_len=17))
        with pytest.raises(ValueError):  # no pages
            eng.submit(GenRequest(prompt=[1, 2], resume_pages=[],
                                  resume_len=8))
        with pytest.raises(ValueError):  # resumed total exceeds max_seq
            eng.submit(GenRequest(prompt=list(range(3, 50)),
                                  resume_pages=list(range(1, 8)),
                                  resume_len=50))
    finally:
        eng.stop()


def test_service_rolling_conversation(monkeypatch):
    """End-to-end rolling serve: consecutive chat turns resume the kept
    pages (prefill = new tokens only), the registry survives many turns,
    and window overflow restarts the conversation without losing
    liveness."""
    import tempfile
    import time as _time

    from swarmdb_tpu.core.runtime import SwarmDB
    from swarmdb_tpu.broker.local import LocalBroker
    from swarmdb_tpu.backend.service import ServingService

    monkeypatch.setenv("SWARMDB_ROLLING_KV", "1")
    monkeypatch.setenv("SWARMDB_PAGED", "1")
    with tempfile.TemporaryDirectory() as d:
        db = SwarmDB(broker=LocalBroker(), save_dir=d)
        db.register_agent("u")
        db.register_agent("bot")
        db.assign_llm_backend("bot", "b0")
        svc = ServingService.from_model_name(
            db, "tiny-debug", backend_id="b0", max_batch=2, max_seq=128,
            decode_chunk=4, page_size=8)
        svc.start(warmup=False)
        try:
            replies = 0
            for turn in range(10):
                db.send_message("u", "bot", f"turn {turn} hello",
                                metadata={"generation": {
                                    "max_new_tokens": 4,
                                    "temperature": 0.0}})
                deadline = _time.time() + 90
                got = False
                while _time.time() < deadline and not got:
                    for m in db.receive_messages("u", timeout=0.5):
                        if m.sender_id == "bot":
                            got = True
                assert got, f"no reply at turn {turn}"
                replies += 1
            resumes = db.metrics.counters["rolling_resumes"].value
            restarts = db.metrics.counters["rolling_restarts"].value
            assert replies == 10
            # most turns resumed; at max_seq=128 the window overflows at
            # least once across 10 growing turns
            assert resumes >= 5, resumes
            assert restarts >= 1, restarts
            # registry custody is consistent: exactly one tracked
            # conversation, not in flight, with live pages
            assert len(svc._rolling) == 1
            st = next(iter(svc._rolling.values()))
            assert st["pages"] and not st["in_flight"]
        finally:
            svc.stop()
            db.close()


def test_rolling_plan_concurrent_turn_is_plain(monkeypatch):
    """A second turn arriving while the conversation's claim is in
    flight must serve PLAIN (no keep_pages): a keep here would let the
    later on_pages overwrite the registry entry and leak the displaced
    pages (review finding)."""
    import tempfile

    from swarmdb_tpu.core.runtime import SwarmDB
    from swarmdb_tpu.broker.local import LocalBroker
    from swarmdb_tpu.backend.service import ServingService
    from swarmdb_tpu.backend.sampling import SamplingParams

    monkeypatch.setenv("SWARMDB_ROLLING_KV", "1")
    monkeypatch.setenv("SWARMDB_PAGED", "1")
    with tempfile.TemporaryDirectory() as d:
        db = SwarmDB(broker=LocalBroker(), save_dir=d)
        db.register_agent("u")
        db.register_agent("bot")
        svc = ServingService.from_model_name(
            db, "tiny-debug", backend_id="b0", max_batch=2, max_seq=64,
            decode_chunk=4, page_size=8)
        try:
            mid = db.send_message("u", "bot", "first")
            msg = db.get_message(mid)
            sp = SamplingParams(max_new_tokens=4)
            key = ("u", "bot")
            mode1, res1, _ = svc._rolling_plan(key, msg, sp)
            assert mode1 == "keep" and res1 is None
            # second turn while the first's claim is in flight
            mid2 = db.send_message("u", "bot", "second")
            mode2, res2, _ = svc._rolling_plan(key, db.get_message(mid2), sp)
            assert mode2 == "plain" and res2 is None
            # first turn completes -> stores pages -> reply finalizes
            svc._rolling_store(key, [1, 2], 12, [])
            msg.metadata["reply_id"] = "r1"
            svc._rolling_finalize(key, msg, "length")
            st = svc._rolling[key]
            assert not st["in_flight"] and st["reply_ids"] == ["r1"]
            # third turn can now RESUME
            mid3 = db.send_message("u", "bot", "third")
            mode3, res3, toks3 = svc._rolling_plan(
                key, db.get_message(mid3), sp)
            assert mode3 == "resume" and res3[:2] == ([1, 2], 12)
            # the plan carries the pool epoch it observed (ADVICE r4 #2)
            assert res3[2] == svc._rolling_epoch()
            assert toks3  # non-empty suffix
        finally:
            db.close()


def test_rolling_soak_page_custody_balances(monkeypatch):
    """Stress the rolling registry with overlapping turns from several
    conversations (forcing concurrent-claim 'plain' turns) and
    overflow restarts — then assert every pool page is accounted for:
    free pages + registry-held pages == all non-trash pages once idle.
    A leak anywhere in the claim/store/finalize/evict custody chain
    shows up as a shortfall here."""
    import tempfile
    import time as _time

    from swarmdb_tpu.core.runtime import SwarmDB
    from swarmdb_tpu.broker.local import LocalBroker
    from swarmdb_tpu.backend.service import ServingService

    monkeypatch.setenv("SWARMDB_ROLLING_KV", "1")
    monkeypatch.setenv("SWARMDB_PAGED", "1")
    with tempfile.TemporaryDirectory() as d:
        db = SwarmDB(broker=LocalBroker(), save_dir=d)
        users = [f"u{i}" for i in range(6)]
        for u in users:
            db.register_agent(u)
        db.register_agent("bot")
        db.assign_llm_backend("bot", "b0")
        svc = ServingService.from_model_name(
            db, "tiny-debug", backend_id="b0", max_batch=4, max_seq=128,
            decode_chunk=4, page_size=8)
        svc.start(warmup=False)
        try:
            # burst sends: several per conversation in flight at once
            for round_ in range(6):
                for u in users:
                    db.send_message(u, "bot", f"r{round_} from {u}",
                                    metadata={"generation": {
                                        "max_new_tokens": 3,
                                        "temperature": 0.0}})
            completed = db.metrics.counters["completed_messages"]
            deadline = _time.time() + 180
            while (completed.value < 6 * len(users)
                   and _time.time() < deadline):
                _time.sleep(0.2)
            assert completed.value >= 6 * len(users), completed.value
            # drain: engine idle, registry settled
            deadline = _time.time() + 30
            while _time.time() < deadline:
                with svc._rolling_lock:
                    busy = any(st.get("in_flight")
                               for st in svc._rolling.values())
                if not busy and not svc.engine._any_active():
                    break
                _time.sleep(0.2)
            # flush/accounting below mutates shared engine state: never
            # proceed against a still-running engine (data race + a
            # misleading "leak" failure)
            assert not busy and not svc.engine._any_active(), \
                "engine failed to drain within 30s"
            alloc = svc.engine.paged.allocator
            # next admission round frees retired slots' pages; force it
            svc.engine.cache["page_table"] = alloc.flush_frees(
                svc.engine.cache["page_table"])
            with svc._rolling_lock:
                held = sum(len(st["pages"]) for st in svc._rolling.values()
                           if st.get("pages"))
            free = alloc.free_count()
            # concurrent-claim 'plain' turns run the NORMAL paged path,
            # whose hash prefix cache also holds pool pages
            hashed = svc.engine._prefix.stats()["cached_pages"]
            assert free + held + hashed == alloc.num_pages - 1, (
                f"page leak: free={free} registry={held} "
                f"hash_cache={hashed} pool={alloc.num_pages - 1}")
        finally:
            svc.stop()
            db.close()


def test_service_rolling_tool_call_turns(monkeypatch):
    """Tool-call turns roll too: a FUNCTION_CALL mid-conversation resumes
    the kept pages ([tool-call]/[tool-result] lines enter the KV via the
    shared _current_lines renderer) and its FUNCTION_RESULT reply id is
    excluded from the next suffix like any reply."""
    import tempfile
    import time as _time

    from swarmdb_tpu.core.runtime import SwarmDB
    from swarmdb_tpu.broker.local import LocalBroker
    from swarmdb_tpu.backend.service import ServingService
    from swarmdb_tpu.core.messages import MessageType

    monkeypatch.setenv("SWARMDB_ROLLING_KV", "1")
    monkeypatch.setenv("SWARMDB_PAGED", "1")
    with tempfile.TemporaryDirectory() as d:
        db = SwarmDB(broker=LocalBroker(), save_dir=d)
        db.register_agent("u")
        db.register_agent("bot")
        db.assign_llm_backend("bot", "b0")
        svc = ServingService.from_model_name(
            db, "tiny-debug", backend_id="b0", max_batch=2, max_seq=256,
            decode_chunk=4, page_size=8)
        svc.start(warmup=False)
        try:
            for turn in range(5):
                if turn % 2:
                    db.send_message(
                        "u", "bot", {"tool": "t", "args": {"i": turn}},
                        message_type=MessageType.FUNCTION_CALL,
                        metadata={"generation": {"max_new_tokens": 3}})
                    want = MessageType.FUNCTION_RESULT
                else:
                    db.send_message("u", "bot", f"chat {turn}",
                                    metadata={"generation": {
                                        "max_new_tokens": 3}})
                    want = MessageType.CHAT
                deadline = _time.time() + 90
                while _time.time() < deadline:
                    if any(m.type == want
                           for m in db.receive_messages("u", timeout=0.5)):
                        break
                else:
                    raise AssertionError(f"no reply at turn {turn}")
            assert db.metrics.counters["rolling_resumes"].value >= 3
            # every reply id so far was recorded for suffix exclusion
            st = next(iter(svc._rolling.values()))
            assert st["reply_ids"], "reply ids not recorded"
        finally:
            svc.stop()
            db.close()


# --------------------------------------------------------- ADVICE r4 fixes


def test_stale_resume_epoch_rejected_at_submit(params):
    """A resume planned against an older pool generation must be refused
    at submit: the reset reclaimed those page ids, so resuming them would
    alias another slot's pages (ADVICE r4 medium #2)."""
    eng = _mk_engine(params)
    try:
        _, pages, written, _ = _gen_keep(eng, list(range(3, 20)), 4)
        req = GenRequest(
            prompt=[5, 6, 7],
            sampling=SamplingParams(max_new_tokens=2, temperature=0.0),
            keep_pages=True,
        )
        req.resume_pages = list(pages)
        req.resume_len = written
        req.resume_epoch = eng.pool_epoch() - 1  # stale by one reset
        with pytest.raises(ValueError, match="stale resume epoch"):
            eng.submit(req)
    finally:
        eng.stop()


def test_stale_resume_epoch_failed_at_admission(params):
    """Epoch is re-validated at ADMISSION too: a pool reset while the
    request sat queued (restart racing a plan) must fail the request
    instead of resuming dangling page ids."""
    import threading

    eng = _mk_engine(params, start=False)
    done = threading.Event()
    out = {}

    def on_done(rid, toks, reason):
        out["reason"] = reason
        done.set()

    req = GenRequest(
        prompt=[5, 6, 7],
        sampling=SamplingParams(max_new_tokens=2, temperature=0.0),
        on_done=on_done, keep_pages=True,
    )
    req.resume_pages = [1, 2]
    req.resume_len = 12
    req.resume_epoch = eng.pool_epoch()  # valid NOW
    eng.submit(req)  # engine not running: stays queued
    eng.paged.allocator.reset()  # pool rebuilt while queued
    eng.start()
    try:
        assert done.wait(60)
        assert out["reason"] == "stale_resume"
        assert eng.metrics.counters["engine_stale_resumes"].value == 1
    finally:
        eng.stop()


def test_pool_pressure_evicts_idle_rolling(monkeypatch):
    """ADVICE r4 medium #1: idle conversations' kept pages must not
    starve new traffic. With the pool sized so a second conversation
    cannot allocate while the first's (idle) pages are parked, admission
    must invoke the pressure hook, evict the idle state, and admit."""
    import tempfile
    import time as _time

    from swarmdb_tpu.core.runtime import SwarmDB
    from swarmdb_tpu.broker.local import LocalBroker
    from swarmdb_tpu.backend.service import ServingService

    monkeypatch.setenv("SWARMDB_ROLLING_KV", "1")
    with tempfile.TemporaryDirectory() as d:
        db = SwarmDB(broker=LocalBroker(), save_dir=d)
        for a in ("u1", "u2", "bot"):
            db.register_agent(a)
        db.assign_llm_backend("bot", "b0")
        db.set_llm_load_balancing(True)
        svc = ServingService.from_model_name(
            db, "tiny-debug", backend_id="b0", max_batch=1, max_seq=64,
            decode_chunk=4, paged=True, page_size=8,
            kv_pool_tokens=64)  # 8 usable pages + trash
        svc.start(warmup=False)
        try:
            db.send_message(
                "u1", "bot", "hello " * 12,
                metadata={"generation": {"max_new_tokens": 4,
                                         "temperature": 0.0}})
            deadline = _time.time() + 120
            while _time.time() < deadline:
                st = svc._rolling.get(("u1", "bot"))
                if (st is not None and st.get("pages")
                        and not st.get("in_flight")):
                    break
                _time.sleep(0.05)
            else:
                raise AssertionError("turn 1 never parked pages")
            held = len(st["pages"])
            free = svc.engine.paged.allocator.free_count()
            # the second request's worst-case footprint must exceed the
            # free pool but fit once the idle pages are reclaimed
            need = svc.engine.paged.allocator.pages_needed(23, 16, 4)
            assert need > free, (need, free)
            assert need <= free + held, (need, free, held)
            db.send_message(
                "u2", "bot", "world " * 12,
                metadata={"generation": {"max_new_tokens": 16,
                                         "temperature": 0.0}})
            deadline = _time.time() + 120
            while _time.time() < deadline:
                if db.metrics.counters["completed_messages"].value >= 2:
                    break
                _time.sleep(0.05)
            else:
                raise AssertionError(
                    "second conversation never completed (pool stalled)")
            assert db.metrics.counters["rolling_evictions"].value >= 1
            assert ("u1", "bot") not in svc._rolling
        finally:
            svc.stop()
            db.close()


# ------------------------------------------------- dense rolling KV (r5)


def _mk_dense_engine(params, pool_pages=64, start=True):
    """DENSE engine (no paged pool) with the prefix machinery — the dense
    rolling path: retirement extracts the lane into prefix-pool pages,
    resume composes them back mid-page."""
    cfg = TINY_DEBUG
    eng = Engine(
        lambda p, t, pos, c: llama.forward(p, cfg, t, pos, c),
        lambda b, s: llama.init_kv_cache(cfg, b, s),
        params, max_batch=BATCH, max_seq=MAX_SEQ, eos_id=-1, seed=0,
        prefill_buckets=[16, 32, 64], decode_chunk=4,
        chunked_fns=(
            lambda p, t, pos, c, hkv, s: llama.forward_chunked(
                p, cfg, t, pos, c, hkv, s),
            lambda b, k: llama.init_chunk_kv(cfg, b, k),
            llama.merge_chunk,
        ),
        prefix_fns=(
            lambda p, t, tab, pl, pk, pv, lp, logits_at=None:
                llama.forward_prefix_lane(p, cfg, t, tab, pl, pk, pv,
                                          lp, logits_at=logits_at),
            lambda n, ps: llama.init_prefix_pool(cfg, n, ps),
        ),
        prefix_pages=pool_pages,
        prefix_page_size=PS,
    )
    if start:
        eng.start()
    return eng


def test_dense_resume_matches_fresh_full_prefill(params):
    """Dense rolling parity: a resumed turn (kept pool pages + suffix-only
    prefill, mid-page boundary) generates exactly the tokens a fresh
    dense engine produces over the full concatenated history."""
    rng = np.random.default_rng(7)
    p1 = rng.integers(3, TINY_DEBUG.vocab_size, size=21).tolist()
    new2 = rng.integers(3, TINY_DEBUG.vocab_size, size=9).tolist()
    new3 = rng.integers(3, TINY_DEBUG.vocab_size, size=5).tolist()

    eng = _mk_dense_engine(params)
    try:
        assert eng.supports_rolling() and not eng.paged
        g1, pages, written, tail = _gen_keep(eng, p1, 7)
        assert written + len(tail) == len(p1) + len(g1)
        assert len(pages) == -(-written // PS)
        # written is mid-page in general — the boundary under test
        g2, pages2, written2, tail2 = _gen_keep(
            eng, tail + new2, 6, resume=(pages, written))
        g3, *_ = _gen_keep(eng, tail2 + new3, 5, resume=(pages2, written2))
    finally:
        eng.stop()

    ref = _mk_dense_engine(params)
    try:
        r2, *_ = _gen_keep(ref, p1 + g1 + new2, 6)
    finally:
        ref.stop()
    assert g2 == r2, (g2, r2)

    ref3 = _mk_dense_engine(params)
    try:
        r3, *_ = _gen_keep(ref3, p1 + g1 + new2 + g2 + new3, 5)
    finally:
        ref3.stop()
    assert g3 == r3, (g3, r3)


def test_dense_resume_frees_superseded_pages(params):
    """Dense retirement extracts a FRESH page set; the resumed turn's
    source pages must return to the pool (custody balance)."""
    rng = np.random.default_rng(11)
    p1 = rng.integers(3, TINY_DEBUG.vocab_size, size=17).tolist()
    eng = _mk_dense_engine(params)
    try:
        free0 = eng._prefix.free_count()
        g1, pages, written, tail = _gen_keep(eng, p1, 5)
        # unlike paged, a dense keep turn ALSO hash-registers its prompt
        # pages (copies — no custody conflict); account for them
        cached1 = eng._prefix.stats()["cached_pages"]
        assert eng._prefix.free_count() == free0 - len(pages) - cached1
        g2, pages2, written2, _ = _gen_keep(
            eng, tail + [9, 9, 9], 5, resume=(pages, written))
        # old kept pages released at retirement, new extraction held
        cached2 = eng._prefix.stats()["cached_pages"]
        assert eng._prefix.free_count() == free0 - len(pages2) - cached2
        eng.rolling_free(pages2)
        assert eng._prefix.free_count() == free0 - cached2
    finally:
        eng.stop()


def test_dense_service_rolling_conversation(monkeypatch):
    """End-to-end dense rolling serve: consecutive turns resume the
    extracted pages on the DEFAULT (non-paged) engine."""
    import tempfile
    import time as _time

    from swarmdb_tpu.core.runtime import SwarmDB
    from swarmdb_tpu.broker.local import LocalBroker
    from swarmdb_tpu.backend.service import ServingService

    monkeypatch.setenv("SWARMDB_ROLLING_KV", "1")
    monkeypatch.delenv("SWARMDB_PAGED", raising=False)
    with tempfile.TemporaryDirectory() as d:
        db = SwarmDB(broker=LocalBroker(), save_dir=d)
        db.register_agent("u")
        db.register_agent("bot")
        db.assign_llm_backend("bot", "b0")
        svc = ServingService.from_model_name(
            db, "tiny-debug", backend_id="b0", max_batch=2, max_seq=128,
            decode_chunk=4, paged=False, page_size=8)
        assert svc.engine.paged is None
        assert svc._rolling is not None, "dense rolling must enable"
        svc.start(warmup=False)
        try:
            for turn in range(8):
                db.send_message("u", "bot", f"turn {turn} hello",
                                metadata={"generation": {
                                    "max_new_tokens": 4,
                                    "temperature": 0.0}})
                deadline = _time.time() + 90
                got = False
                while _time.time() < deadline and not got:
                    for m in db.receive_messages("u", timeout=0.5):
                        got = got or m.sender_id == "bot"
                assert got, f"no reply at turn {turn}"
            resumes = db.metrics.counters["rolling_resumes"].value
            assert resumes >= 4, resumes
            st = next(iter(svc._rolling.values()))
            assert st["pages"] and not st["in_flight"]
        finally:
            svc.stop()
            db.close()


def test_dense_pool_pressure_evicts_idle_rolling(monkeypatch):
    """Dense counterpart of the paged pressure test: when retirement
    extraction cannot acquire pages because idle conversations hold the
    pool, the pressure hook evicts them and the extraction retries."""
    import tempfile
    import time as _time

    from swarmdb_tpu.core.runtime import SwarmDB
    from swarmdb_tpu.broker.local import LocalBroker
    from swarmdb_tpu.backend.service import ServingService

    monkeypatch.setenv("SWARMDB_ROLLING_KV", "1")
    monkeypatch.delenv("SWARMDB_PAGED", raising=False)
    # pool of 8 usable pages (SWARMDB_PREFIX_TOKENS = 64, ps 8): one
    # conversation's kept state (~5 pages) + a second's extraction
    # cannot both fit
    monkeypatch.setenv("SWARMDB_PREFIX_TOKENS", "64")
    with tempfile.TemporaryDirectory() as d:
        db = SwarmDB(broker=LocalBroker(), save_dir=d)
        for a in ("u1", "u2", "bot"):
            db.register_agent(a)
        db.assign_llm_backend("bot", "b0")
        svc = ServingService.from_model_name(
            db, "tiny-debug", backend_id="b0", max_batch=1, max_seq=64,
            decode_chunk=4, paged=False, page_size=8)
        svc.start(warmup=False)
        try:
            meta = {"generation": {"max_new_tokens": 4, "temperature": 0.0}}
            db.send_message("u1", "bot", "hello " * 12, metadata=dict(meta))
            deadline = _time.time() + 120
            while _time.time() < deadline:
                st = svc._rolling.get(("u1", "bot"))
                if (st is not None and st.get("pages")
                        and not st.get("in_flight")):
                    break
                _time.sleep(0.05)
            else:
                raise AssertionError("turn 1 never parked pages")
            db.send_message("u2", "bot", "world " * 14,
                            metadata={"generation": {"max_new_tokens": 16,
                                                     "temperature": 0.0}})
            deadline = _time.time() + 120
            while _time.time() < deadline:
                st2 = svc._rolling.get(("u2", "bot"))
                if (st2 is not None and st2.get("pages")
                        and not st2.get("in_flight")):
                    break
                _time.sleep(0.05)
            else:
                raise AssertionError("second conversation never rolled")
            assert db.metrics.counters["rolling_evictions"].value >= 1
            assert ("u1", "bot") not in svc._rolling
        finally:
            svc.stop()
            db.close()
