"""swarmtier (ISSUE 19): the three-tier conversation-state hierarchy.

The correctness bar: a conversation's token stream is IDENTICAL no
matter which tier its state took — hot resume, demote->promote (warm),
or demote->cold-resume (re-prefill from the broker log). Plus the pure
victim-selection policy, the backpressure gate's demote hysteresis
(no thrash), and a pagecheck-clean demote/promote chaos drill.
"""

import tempfile
import time as _time

import pytest

from swarmdb_tpu.backend.tiering import select_victims


# ----------------------------------------------------------- victim policy


class TestSelectVictims:
    NOW = 1000.0

    def test_coldest_first_by_last_touch(self):
        cands = [("a", 2, 990.0, 5), ("b", 2, 900.0, 5),
                 ("c", 2, 950.0, 5)]
        assert select_victims(cands, 2, self.NOW, 1.0) == ["b"]
        assert select_victims(cands, 4, self.NOW, 1.0) == ["b", "c"]

    def test_touch_count_breaks_ties(self):
        cands = [("hotter", 1, 900.0, 50), ("colder", 1, 900.0, 2)]
        assert select_victims(cands, 1, self.NOW, 1.0) == ["colder"]

    def test_min_idle_guard_excludes_recent(self):
        cands = [("fresh", 4, self.NOW - 0.1, 0),
                 ("idle", 1, self.NOW - 10.0, 0)]
        # the recently-touched entry is never picked, even though it
        # alone covers the need
        assert select_victims(cands, 4, self.NOW, 1.0) == ["idle"]

    def test_stops_once_need_covered(self):
        cands = [("a", 3, 900.0, 0), ("b", 3, 901.0, 0),
                 ("c", 3, 902.0, 0)]
        assert select_victims(cands, 4, self.NOW, 0.0) == ["a", "b"]

    def test_returns_all_eligible_on_shortfall(self):
        cands = [("a", 1, 900.0, 0), ("b", 1, 901.0, 0)]
        assert select_victims(cands, 100, self.NOW, 0.0) == ["a", "b"]

    def test_empty(self):
        assert select_victims([], 5, self.NOW, 0.0) == []


# ----------------------------------------------------- gate demote hysteresis


def _mk_gate_probe(bp_low, bp_demote, bp_high):
    """A minimal object running the engine's demote-gate state machine
    exactly as `_backpressure` does (hysteresis band low..demote)."""
    class _G:
        def __init__(self):
            self._bp_low, self._bp_demote = bp_low, bp_demote
            self._bp_high = bp_high
            self._tier_demoting = False
            self.signals = []

        def step(self, util):
            if self._tier_demoting:
                if util <= self._bp_low:
                    self._tier_demoting = False
            elif util >= self._bp_demote:
                self._tier_demoting = True
            if self._tier_demoting:
                self.signals.append(util)

    return _G()


def test_demote_gate_hysteresis_no_thrash():
    """Utilization oscillating just under the demote watermark must not
    flap the demote signal on/off every step: once tripped, demotion
    stays engaged until util falls to the LOW watermark."""
    g = _mk_gate_probe(0.60, 0.85, 0.92)
    for u in (0.70, 0.84, 0.80, 0.84):  # never reaches demote mark
        g.step(u)
    assert g.signals == []
    g.step(0.86)            # trips
    g.step(0.70)            # inside the band: STAYS engaged
    g.step(0.61)            # still above low: stays engaged
    assert g.signals == [0.86, 0.70, 0.61]
    g.step(0.59)            # below low: disengages
    g.step(0.84)            # below demote mark again: stays off
    assert g.signals == [0.86, 0.70, 0.61]


def test_demote_watermark_env_parsing(monkeypatch):
    """SWARMDB_TIER_DEMOTE >= 1.0 disables; otherwise clamped into the
    [low, high] band (a demote mark above shed would never fire)."""
    from swarmdb_tpu.backend.service import build_backend_engine

    def mk():
        return build_backend_engine(
            "tiny-debug", paged=True, max_batch=2, max_seq=64, page_size=8,
            kv_pool_tokens=128, decode_chunk=4)[0]

    monkeypatch.setenv("SWARMDB_TIER_DEMOTE", "1.0")
    assert mk()._bp_demote >= 1.0          # disabled, not clamped
    monkeypatch.setenv("SWARMDB_TIER_DEMOTE", "0.05")
    eng = mk()
    assert eng._bp_demote == eng._bp_low   # clamped up to low
    monkeypatch.setenv("SWARMDB_TIER_DEMOTE", "0.99")
    eng = mk()
    assert eng._bp_demote == eng._bp_high  # clamped down to shed mark


# ------------------------------------------------- service-level tier cycles


def _mk_tier_service(db, max_seq=256, warm_mb=None):
    from swarmdb_tpu.backend.service import ServingService

    svc = ServingService.from_model_name(
        db, "tiny-debug", backend_id="b0", max_batch=2, max_seq=max_seq,
        decode_chunk=4, page_size=8)
    assert svc._tier is not None, "tier manager must attach"
    svc._tier.min_idle_s = 0.0  # every parked conversation is eligible
    return svc


def _chat_turns(db, svc, user, n_turns, max_new=4, on_turn=None):
    """Drive n_turns greedy turns; returns the bot reply texts."""
    replies = []
    for turn in range(n_turns):
        if on_turn is not None:
            on_turn(turn)
        db.send_message(user, "bot", f"turn {turn} from {user}",
                        metadata={"generation": {
                            "max_new_tokens": max_new,
                            "temperature": 0.0}})
        deadline = _time.time() + 90
        got = None
        while _time.time() < deadline and got is None:
            for m in db.receive_messages(user, timeout=0.5):
                if m.sender_id == "bot":
                    got = m
        assert got is not None, f"no reply at turn {turn} for {user}"
        replies.append(got.content)
    return replies


def _fresh_db(d):
    from swarmdb_tpu.core.runtime import SwarmDB
    from swarmdb_tpu.broker.local import LocalBroker

    db = SwarmDB(broker=LocalBroker(), save_dir=d)
    db.register_agent("u")
    db.register_agent("bot")
    db.assign_llm_backend("bot", "b0")
    return db


def _wait_parked(svc, key, timeout=60):
    deadline = _time.time() + timeout
    while _time.time() < deadline:
        with svc._rolling_lock:
            st = svc._rolling.get(key)
            if (st is not None and st.get("pages")
                    and not st.get("in_flight")):
                return st
        _time.sleep(0.05)
    raise AssertionError(f"{key} never parked device pages")


def _demote_all(svc):
    """Force-demote every idle device-resident conversation (the same
    call the pool-pressure hook makes; engine is idle so the gathers
    race nothing)."""
    with svc._rolling_lock:
        return svc._tier.demote_now(10 ** 6)


@pytest.fixture()
def rolling_env(monkeypatch):
    monkeypatch.setenv("SWARMDB_ROLLING_KV", "1")
    monkeypatch.setenv("SWARMDB_PAGED", "1")
    monkeypatch.setenv("SWARMDB_TIER", "1")


@pytest.mark.slow  # two full services; rides CI's pagecheck job, not tier-1
def test_demote_promote_bit_identical(rolling_env):
    """Greedy decode across a demote->promote (warm) cycle must equal
    the never-demoted conversation token for token: promotion re-inserts
    the exact spilled bytes, so the chunk-boundary decode that follows
    sees bit-identical KV."""
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        db = _fresh_db(d1)
        svc = _mk_tier_service(db)
        svc.start(warmup=False)
        try:
            key = ("u", "bot")

            def demote_between(turn):
                if turn == 0:
                    return
                st = _wait_parked(svc, key)
                freed = _demote_all(svc)
                assert freed > 0, "demotion freed nothing"
                with svc._rolling_lock:
                    st = svc._rolling[key]
                    assert st.get("host") and st.get("pages") is None
                assert svc._tier.store.has(key)

            got = _chat_turns(db, svc, "u", 4, on_turn=demote_between)
            assert svc._tier.promotions >= 3, svc._tier.promotions
            assert svc._tier.demotions >= 3, svc._tier.demotions
            # every resumed turn was a WARM hit, not a cold restart
            assert db.metrics.counters["rolling_resumes"].value >= 3
            assert svc._tier.cold_resumes == 0
        finally:
            svc.stop()
            db.close()

        # reference: identical turns, no demotion anywhere
        db2 = _fresh_db(d2)
        svc2 = _mk_tier_service(db2)
        svc2.start(warmup=False)
        try:
            want = _chat_turns(db2, svc2, "u", 4)
            assert svc2._tier.demotions == 0
        finally:
            svc2.stop()
            db2.close()
    assert got == want, (got, want)


@pytest.mark.slow  # two full services; rides CI's pagecheck job, not tier-1
def test_demote_cold_resume_bit_identical(rolling_env, monkeypatch):
    """Greedy decode across a demote that falls THROUGH the warm store
    (capacity zero: entry goes straight to cold) must match the replay
    contract PR 8 proved: a cold resume re-prefills the rendered broker
    log, so its reply is bit-identical to a service that builds the full
    prompt from the log every turn (rolling disabled). NOT compared
    against an uninterrupted rolling session — live resume keeps the
    model's raw reply tokens in KV, while replay re-renders them as
    history lines, a deliberately different (deterministic) stream."""
    with tempfile.TemporaryDirectory() as d1, \
            tempfile.TemporaryDirectory() as d2:
        db = _fresh_db(d1)
        svc = _mk_tier_service(db)
        svc.start(warmup=False)
        try:
            key = ("u", "bot")

            def cold_between(turn):
                if turn == 0:
                    return
                _wait_parked(svc, key)
                # an entry bigger than the whole store is evicted by
                # put() itself -> _finish_cold: registry entry dies,
                # the cold ledger remembers the footprint
                svc._tier.store.capacity_bytes = 1
                _demote_all(svc)
                with svc._rolling_lock:
                    assert key not in svc._rolling
                assert not svc._tier.store.has(key)

            got = _chat_turns(db, svc, "u", 3, on_turn=cold_between)
            assert svc._tier.cold_resumes >= 2, svc._tier.cold_resumes
            assert svc._tier.promotions == 0
            # cold TTFT histogram observed the resumed turns
            h = db.metrics.latencies.get("tier_ttft_cold_s")
            assert h is not None and h.count() >= 2
        finally:
            svc.stop()
            db.close()

        # reference: the pure replay path — every turn is a full-prompt
        # prefill from the broker log, exactly what each cold resume ran
        from swarmdb_tpu.backend.service import ServingService

        monkeypatch.setenv("SWARMDB_ROLLING_KV", "0")
        db2 = _fresh_db(d2)
        svc2 = ServingService.from_model_name(
            db2, "tiny-debug", backend_id="b0", max_batch=2, max_seq=256,
            decode_chunk=4, page_size=8)
        svc2.start(warmup=False)
        try:
            assert svc2._rolling is None
            want = _chat_turns(db2, svc2, "u", 3)
        finally:
            svc2.stop()
            db2.close()
    assert got == want, (got, want)


def test_warm_store_eviction_goes_cold(rolling_env):
    """When a newer demotion LRU-evicts an older warm entry, the older
    conversation leaves the hierarchy: registry entry dropped, cold
    ledger charged, warm_evictions counted — and its next turn still
    completes (cold resume liveness)."""
    with tempfile.TemporaryDirectory() as d:
        db = _fresh_db(d)
        db.register_agent("u2")
        svc = _mk_tier_service(db)
        svc.start(warmup=False)
        try:
            _chat_turns(db, svc, "u", 1)
            st_u = _wait_parked(svc, ("u", "bot"))
            # size the store to hold exactly u's footprint, then demote
            from swarmdb_tpu.ops.paged_kv import pool_page_bytes
            page_bytes = (pool_page_bytes(svc.engine.cache["k"])
                          + pool_page_bytes(svc.engine.cache["v"]))
            svc._tier.store.capacity_bytes = len(st_u["pages"]) * page_bytes
            assert _demote_all(svc) > 0
            assert svc._tier.store.has(("u", "bot"))
            # second conversation demotes on top: u must fall out cold
            _chat_turns(db, svc, "u2", 1)
            _wait_parked(svc, ("u2", "bot"))
            _demote_all(svc)
            assert not svc._tier.store.has(("u", "bot"))
            assert svc._tier.warm_evictions >= 1
            with svc._rolling_lock:
                assert ("u", "bot") not in svc._rolling
            # liveness: u comes back (cold) and still gets a reply
            _chat_turns(db, svc, "u", 1)
            assert svc._tier.cold_resumes >= 1
        finally:
            svc.stop()
            db.close()


def test_tier_status_and_memprof_loop(rolling_env):
    """status() is the single intro surface (bench, /admin/tiers,
    /metrics all read it): tier page gauges, counters, warm_hit_rate —
    and the swarmmem loop closure sees the SAME numbers via
    memprof().tier_validation()."""
    with tempfile.TemporaryDirectory() as d:
        db = _fresh_db(d)
        svc = _mk_tier_service(db)
        svc.start(warmup=False)
        try:
            _chat_turns(db, svc, "u", 2)
            _wait_parked(svc, ("u", "bot"))
            _demote_all(svc)
            s = svc._tier.status()
            assert s["enabled"] is True
            assert set(s["pages"]) == {"hot", "warm", "cold"}
            assert s["pages"]["warm"] > 0
            assert s["counters"]["demotions"] >= 1
            assert 0.0 <= s["warm_hit_rate"] <= 1.0
            assert s["config"]["warm_capacity_bytes"] > 0
            # db metrics mirror (flag-independent /metrics source)
            assert db.metrics.counters["tier_demotions"].value \
                == s["counters"]["demotions"]
            # swarmmem loop closure reads the same status
            from swarmdb_tpu.obs.memprof import memprof
            tv = memprof().tier_validation()
            assert tv is not None
            assert tv["promotions"] == s["counters"]["promotions"]
            assert tv["cold_resumes"] == s["counters"]["cold_resumes"]
            assert tv["warm_pages"] == s["pages"]["warm"]
            # service health embeds it too
            assert svc.health()["tier"]["enabled"] is True
        finally:
            svc.stop()
            db.close()


def test_tier_disabled_by_env(monkeypatch):
    monkeypatch.setenv("SWARMDB_ROLLING_KV", "1")
    monkeypatch.setenv("SWARMDB_PAGED", "1")
    monkeypatch.setenv("SWARMDB_TIER", "0")
    with tempfile.TemporaryDirectory() as d:
        db = _fresh_db(d)
        from swarmdb_tpu.backend.service import ServingService

        svc = ServingService.from_model_name(
            db, "tiny-debug", backend_id="b0", max_batch=2, max_seq=128,
            decode_chunk=4, page_size=8)
        try:
            assert svc._tier is None
            assert svc.health()["tier"] == {"enabled": False}
        finally:
            db.close()


# --------------------------------------------------- pagecheck chaos drill


@pytest.mark.slow  # the CI pagecheck job runs this under the flag
def test_demote_promote_chaos_pagecheck_clean(rolling_env, monkeypatch,
                                              tmp_path):
    """Chaos drill under the sanitizer: overlapping conversations with
    forced demotions between turns — every page's cross-tier custody
    transition (on_demote -> host_resident -> on_promote / on_host_drop)
    must check out. Zero violations."""
    monkeypatch.setenv("SWARMDB_PAGECHECK", "1")
    monkeypatch.setenv("SWARMDB_FLIGHT_DIR", str(tmp_path))
    from swarmdb_tpu.obs import pagecheck

    pagecheck.registry().reset()
    try:
        with tempfile.TemporaryDirectory() as d:
            db = _fresh_db(d)
            users = ["u", "ua", "ub"]
            for u in users[1:]:
                db.register_agent(u)
            svc = _mk_tier_service(db, max_seq=128)
            svc.start(warmup=False)
            try:
                for round_ in range(3):
                    for u in users:
                        db.send_message(
                            u, "bot", f"r{round_} {u} hello",
                            metadata={"generation": {
                                "max_new_tokens": 3,
                                "temperature": 0.0}})
                    completed = db.metrics.counters["completed_messages"]
                    deadline = _time.time() + 120
                    want = (round_ + 1) * len(users)
                    while (completed.value < want
                           and _time.time() < deadline):
                        _time.sleep(0.1)
                    assert completed.value >= want, completed.value
                    # settle, then demote everything idle; shrink the
                    # store every other round so some entries fall cold
                    for u in users:
                        k = (u, svc._rolling and "bot")
                        try:
                            _wait_parked(svc, (u, "bot"), timeout=30)
                        except AssertionError:
                            pass  # already demoted / restarted
                    if round_ == 1:
                        svc._tier.store.capacity_bytes = 1
                    _demote_all(svc)
                assert svc._tier.demotions + svc._tier.cold_resumes > 0
                assert pagecheck.registry().violations() == [], \
                    pagecheck.registry().violations()
            finally:
                svc.stop()
                db.close()
    finally:
        pagecheck.registry().reset()
