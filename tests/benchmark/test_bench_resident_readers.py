"""The three readers of the resident loop's hand-off (PR 40), each on a
canned trace, canned counters or hand-made spans: what it reads, 0 where
there was something to count and none of it, and ``None`` where the
program has no such event, counter or argument (the parent commit). And
one whole tiny ``run.py`` on the CPU, as it is, that reports the two a CPU
run can."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import spec as specs  # noqa: E402
from benchmark.harness import trace_reduce  # noqa: E402

SPEC = ROOT / "tests" / "benchmark" / "tiny" / "spec-resident.json"
FIXTURE = Path(__file__).parent / "fixtures" / "trace_callback.json"
T0, SECONDS = 1000.0, 50.0
ENGINE, CALLBACK = 11, 12           # thread ids


def read(name, ctx):
    return specs.load_reader(name).read(ctx)


def ctx_of(**more):
    ctx = {"t0": T0, "seconds": SECONDS, "spans": [], "trace": None,
           "ring_stats": [], "notes": {}, "counters": {},
           "trace_counters": {}}
    ctx.update(more)
    return ctx


# ------------------------------------ decode_callback_wait_ms_per_chunk

@pytest.fixture(scope="module")
def reduced():
    with open(FIXTURE) as f:
        return trace_reduce.reduce(json.load(f))


def test_callback_wait_is_the_callbacks_time_over_the_chunks(reduced):
    # three chunks in two sessions, four operations a chunk (send,
    # send-done, recv, recv-done): 5.0 + 6.0 + 1.0 ms of callback in all
    assert reduced["kernels"]["io_callback"] == {
        "seconds": pytest.approx(0.012), "calls": 12}
    ctx = ctx_of(trace=reduced,
                 trace_counters={"engine_resident_chunks": 3})
    assert read("decode_callback_wait_ms_per_chunk",
                ctx) == pytest.approx(4.0)
    assert ctx["notes"]["decode_callback_wait_ms_per_chunk"] == {
        "seconds": pytest.approx(0.012), "events": 12, "chunks": 3}
    # the wait is inside busy time: the while's leaves cover it, and the
    # decode program's device time holds it
    assert reduced["busy_s"] == pytest.approx(0.072)
    assert reduced["programs"]["decode_resident_greedy"][
        "busy_s"] == pytest.approx(0.072)


def test_callback_wait_is_none_without_a_trace_a_callback_or_a_chunk(
        reduced):
    chunks = {"engine_resident_chunks": 3}
    assert read("decode_callback_wait_ms_per_chunk",
                ctx_of(trace_counters=chunks)) is None
    scan = dict(reduced, kernels={k: v for k, v in
                                  reduced["kernels"].items()
                                  if k != "io_callback"})
    ctx = ctx_of(trace=scan, trace_counters=chunks)
    assert read("decode_callback_wait_ms_per_chunk", ctx) is None
    assert read("decode_callback_wait_ms_per_chunk",
                ctx_of(trace=reduced, trace_counters={})) is None
    assert not ctx["notes"]


# -------------------------------------------- resident_stale_vote_share

def test_stale_share_is_stale_votes_over_chunks():
    assert read("resident_stale_vote_share", ctx_of(counters={
        "resident_votes_stale": 3, "engine_resident_chunks": 600,
    })) == pytest.approx(0.5)


def test_stale_share_is_zero_where_chunks_ran_and_no_vote_went_stale():
    got = read("resident_stale_vote_share", ctx_of(counters={
        "resident_votes_stale": 0, "engine_resident_chunks": 600}))
    assert got == 0.0 and got is not None


@pytest.mark.parametrize("counters", [
    {},                                       # nothing ran
    {"engine_resident_chunks": 600},          # the parent: no such counter
    {"resident_votes_stale": 0},              # the scan path: no chunk
    {"resident_votes_stale": 0, "engine_resident_chunks": 0}])
def test_stale_share_is_none_without_the_counter_or_a_chunk(counters):
    assert read("resident_stale_vote_share",
                ctx_of(counters=counters)) is None


# --------------------------------------------------- emit_behind_ms_p90

def emit(start, behind_us=None, tid=ENGINE, **args):
    if behind_us is not None:
        args["behind_us"] = behind_us
    return {"name": "engine.emit", "cat": "engine", "rid": None,
            "start_s": T0 + start, "dur_us": 4000.0, "tid": tid,
            "thread": "engine", "args": dict(args, step=3, chunk=0, live=2)}


def ring(tid, lost=0, oldest_end=-5.0, cap=8192):
    return {"tid": tid, "thread": f"t{tid}", "written": cap + lost,
            "capacity": cap, "lost": lost, "oldest_end_s": T0 + oldest_end}


def test_behind_is_the_windows_p90_in_ms():
    spans = [emit(1.0 + i, behind_us=100 * (i + 1)) for i in range(10)]
    spans.append(emit(-3.0, behind_us=90_000))      # before the window
    spans.append(emit(55.0, behind_us=90_000))      # after it
    assert read("emit_behind_ms_p90",
                ctx_of(spans=spans)) == pytest.approx(0.9)


def test_behind_is_none_where_the_callback_emits_itself():
    # the parent commit: engine.emit on the callback's thread, no stamp
    spans = [emit(1.0 + i, tid=CALLBACK) for i in range(5)]
    ctx = ctx_of(spans=spans)
    assert read("emit_behind_ms_p90", ctx) is None and not ctx["notes"]
    assert read("emit_behind_ms_p90", ctx_of()) is None


def test_behind_is_none_with_a_note_where_the_ring_was_lapped():
    spans = [emit(1.0 + i, behind_us=200) for i in range(5)]
    ctx = ctx_of(spans=spans,
                 ring_stats=[ring(ENGINE, lost=40, oldest_end=0.5)])
    assert read("emit_behind_ms_p90", ctx) is None
    assert "lapped inside the window" in ctx["notes"][
        "emit_behind_ms_p90"]["unread"]


# ------------------------------------------------------- a whole tiny run

def test_a_whole_tiny_run_reports_the_two_a_cpu_run_can(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"),
               TMPDIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    env.pop("SWARMDB_TRACE", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--spec",
         str(SPEC), "--workload", "tiny.chat", "--platform", "cpu",
         "--seed", str(2 ** 31 + 40), "--seconds", "3", "--trace", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    out, facts = json.loads(lines[-1]), json.loads(lines[-2])
    assert out["correct"] is True and out["failed"] == 0
    m = out["metrics"]
    # no device trace on the CPU: the callback's wait is a chip number
    assert "decode_callback_wait_ms_per_chunk" not in m
    assert m["resident_stale_vote_share"]["value"] == 0.0
    assert 0.0 <= m["emit_behind_ms_p90"]["value"] < 1000.0
    c = facts["counters_window"]
    assert c["resident_votes_stale"] == 0
    assert c["engine_resident_chunks"] > 0
