"""The engine loop on the trace (ISSUE 25): phase spans with the loop
step that caused them, the counters where the work happens, names on the
jitted decode programs, and the tracer's second sink."""

import sys
import threading

import pytest

from swarmdb_tpu.backend.engine import (DECODE_PROGRAM_NAMES,
                                        RESIDENT_PROGRAM_NAMES, GenRequest)
from swarmdb_tpu.backend.sampling import SamplingParams
from swarmdb_tpu.backend.service import build_backend_engine
from swarmdb_tpu.models.configs import get_config
from swarmdb_tpu.obs import TRACER, SpanTracer

CFG = get_config("tiny-debug")
PHASES = {"engine.wait", "engine.admission", "engine.admission.reclaim",
          "engine.admission.plan", "engine.admission.pack",
          "engine.admission.dispatch", "engine.session", "engine.emit",
          "engine.first_token", "engine.compile"}
PER_REQUEST = {"engine.admit", "engine.prefill", "engine.first_token"}


@pytest.fixture(scope="module")
def run():
    """Two rounds of traffic through a tiny paged engine that was not
    warmed (so its first steps compile): three requests at once, then one
    more after they retired (so their pages are reclaimed)."""
    eng, _tok = build_backend_engine(CFG, max_batch=4, max_seq=96,
                                     paged=True, page_size=16)
    TRACER.reset()
    was = TRACER.enabled
    TRACER.set_enabled(True)
    eng.start()
    try:
        done, left = threading.Event(), [3]

        def on_done(_rid, _toks, _reason):
            left[0] -= 1
            if not left[0]:
                done.set()

        rids = [eng.submit(GenRequest(
            prompt=p, sampling=SamplingParams(max_new_tokens=12),
            on_done=on_done, metadata={"message_id": f"m{i}"}))
            for i, p in enumerate([[1, 5, 9, 2, 7] * 3, [4] * 37,
                                   [2, 3] * 11])]
        assert done.wait(180)
        eng.generate_sync([1, 5, 9, 2, 7] * 4,
                          SamplingParams(max_new_tokens=9), timeout=180)
    finally:
        eng.stop()
        TRACER.set_enabled(was)
    spans = [e for e in TRACER.snapshot() if e["cat"] == "engine"]
    return eng, rids, spans


def test_every_phase_is_recorded_with_its_step(run):
    _eng, _rids, spans = run
    by_name = {}
    for e in spans:
        by_name.setdefault(e["name"], []).append(e)
    assert PHASES <= set(by_name)
    for name in PHASES | {"engine.admit", "engine.prefill"}:
        for e in by_name[name]:
            assert isinstance(e["args"]["step"], int) and e["args"]["step"] > 0
    # the old per-chunk and per-sync spans stay as they were
    assert {"engine.decode_chunk", "engine.host_sync"} <= set(by_name)
    adm = by_name["engine.admission"]
    assert sum(e["args"]["admitted"] for e in adm) == 4
    assert all(e["args"]["queued_after"] == 0 for e in adm)
    # a round's plan counts its rows once, wherever they were planned: a
    # plan made while a session ran (``early``, ISSUE 45) is taken up by
    # the next round's, which says how many of its rows came that way
    plans = [e["args"] for e in by_name["engine.admission.plan"]]
    assert sum(a["rows"] for a in plans if not a.get("early")) == 4
    assert (sum(a["planned"] for a in plans if a.get("early"))
            == sum(a["ahead"] for a in plans if not a.get("early")))
    assert {e["args"]["kind"]
            for e in by_name["engine.admission.dispatch"]} == {"ragged"}
    waves = [e["args"]["wave"] for e in by_name["engine.admission.dispatch"]]
    assert waves == list(range(1, len(waves) + 1))
    assert [e["args"]["wave"]
            for e in by_name["engine.admission.pack"]] == waves
    assert all(0 < e["args"]["filled"] <= e["args"]["width"]
               for e in by_name["engine.admission.pack"])
    assert by_name["engine.admission.reclaim"][0]["args"]["slots"] == 3
    session = by_name["engine.session"][0]["args"]
    assert session["variant"] == "decode_resident_greedy"
    assert session["slots"] == 3 and session["carried"] == 0
    assert session["chunks"] >= 1
    assert by_name["engine.compile"][0]["args"]["delta"] >= 1
    # a phase nests inside the round that opened it
    for e in by_name["engine.admission.dispatch"]:
        outer = [a for a in adm if a["args"]["step"] == e["args"]["step"]]
        assert len(outer) == 1
        assert outer[0]["start_s"] <= e["start_s"]
        assert (e["start_s"] + e["dur_us"] * 1e-6
                <= outer[0]["start_s"] + outer[0]["dur_us"] * 1e-6 + 1e-6)


def test_a_requests_spans_share_rid_and_name_a_real_step(run):
    _eng, rids, spans = run
    admissions = {e["args"]["step"] for e in spans
                  if e["name"] == "engine.admission"}
    sessions = {e["args"]["step"] for e in spans
                if e["name"] == "engine.session"}
    for i, rid in enumerate(rids):
        mine = {e["name"]: e for e in spans
                if e["rid"] == rid and e["name"] in PER_REQUEST}
        assert set(mine) == PER_REQUEST
        for e in mine.values():
            assert e["args"]["step"] in admissions
            assert e["args"]["step"] in sessions
        assert mine["engine.prefill"]["args"]["mid"] == f"m{i}"
        first = mine["engine.first_token"]
        assert first["args"]["mid"] == f"m{i}"
        assert first["args"]["cached_tokens"] == 0
        assert first["args"]["new_tokens"] == (15, 37, 22)[i]
        assert mine["engine.prefill"]["args"]["new_tokens"] == (
            first["args"]["new_tokens"])
        # admission -> first token starts where the prefill span starts
        assert first["start_s"] == pytest.approx(
            mine["engine.prefill"]["start_s"], abs=1e-3)
        assert first["dur_us"] >= mine["engine.prefill"]["dur_us"]


def test_work_counters_obey_their_bounds(run):
    eng, _rids, spans = run
    c = {k: v.value for k, v in eng.metrics.counters.items()}
    assert c["prefill_device_waves"] == sum(
        e["name"] == "engine.admission.dispatch" for e in spans)
    assert c["prefill_device_waves"] >= c["engine_admission_waves"] == 2
    assert 0 < c["decode_slot_chunks"] <= (c["engine_resident_chunks"]
                                           * eng.max_batch)
    assert c["decode_slot_chunks"] == sum(
        e["args"]["live"] for e in spans if e["name"] == "engine.emit")
    assert 0 < c["kv_page_chunks_written"] <= c["kv_page_chunks_reserved"]


def test_decode_programs_have_names(run):
    eng = run[0]
    names = [fn.__name__ for fn in
             eng._decode_variants + eng._resident_variants]
    assert names == list(DECODE_PROGRAM_NAMES + RESIDENT_PROGRAM_NAMES)
    for name in names:
        assert "decode" in name and "prefill" not in name
        assert "unknown" not in name and "lambda" not in name
    import jax.numpy as jnp

    from swarmdb_tpu.ops import paged_kv

    lowered = paged_kv._set_page_table_rows.lower(
        jnp.zeros((4, 6), jnp.int32), jnp.zeros((4,), jnp.int32),
        jnp.zeros((4, 6), jnp.int32)).as_text()
    assert "jit__set_page_table_rows" in lowered


def test_phases_record_without_jax(monkeypatch):
    """``obs`` stays importable and recording where jax was never loaded:
    the ring is then the only sink."""
    monkeypatch.delitem(sys.modules, "jax")
    tracer = SpanTracer(capacity_per_thread=16, enabled=True)
    outer = tracer.phase_begin("engine.admission")
    inner = tracer.phase_begin("engine.admission.plan")
    tracer.phase_end(inner, "engine.admission.plan", cat="engine",
                     args={"step": 1})
    tracer.phase_end(outer, "engine.admission", cat="engine",
                     args={"step": 1})
    assert tracer._annotation is None
    assert [e["name"] for e in tracer.snapshot()] == [
        "engine.admission", "engine.admission.plan"]


def test_phases_open_profiler_annotations_and_close_leaked_ones():
    import jax  # noqa: F401  (loaded: the tracer finds it)

    tracer = SpanTracer(capacity_per_thread=16, enabled=True)
    outer = tracer.phase_begin("engine.admission")
    assert tracer._annotation is jax.profiler.TraceAnnotation
    tracer.phase_begin("engine.admission.dispatch")   # raised inside
    assert len(tracer._local.phases) == 2
    tracer.phase_end(outer, "engine.admission", cat="engine")
    assert tracer._local.phases == []
    # SWARMDB_TRACE=0: neither sink
    tracer.set_enabled(False)
    assert tracer.phase_begin("engine.wait") == 0
    tracer.phase_end(0, "engine.wait")
    assert tracer._local.phases == []
    assert [e["name"] for e in tracer.snapshot()] == ["engine.admission"]


def test_ring_stats_tell_a_lapped_ring():
    tracer = SpanTracer(capacity_per_thread=16, enabled=True)
    for i in range(20):
        tracer.span_end(tracer.span_begin(), f"s{i}")
    (ring,) = tracer.ring_stats()
    assert (ring["written"], ring["capacity"], ring["lost"]) == (20, 16, 4)
    oldest = tracer.snapshot()[0]
    assert oldest["name"] == "s4"
    assert ring["oldest_end_s"] == pytest.approx(
        oldest["start_s"] + oldest["dur_us"] * 1e-6, abs=1e-6)
