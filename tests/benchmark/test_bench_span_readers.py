"""The per-layer readers of the program's phase spans and work counters
(PR 25), each on a synthetic span list or counter set: what it reads, what
it leaves out, and ``None`` where the program gives nothing to read or a
ring was lapped inside the window."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import spec as specs  # noqa: E402

# tiny/spec.json with this PR's seven metrics at the end of per_layer
SPEC = ROOT / "tests" / "benchmark" / "tiny" / "spec-phases.json"

T0, SECONDS = 1000.0, 50.0
ENGINE, CALLBACK = 11, 12           # thread ids


def span(name, start, dur_ms, tid=ENGINE, rid=None, cat="engine", **args):
    return {"name": name, "cat": cat, "rid": rid, "start_s": T0 + start,
            "dur_us": dur_ms * 1e3, "tid": tid,
            "thread": "swarmdb-engine" if tid == ENGINE else "callback",
            "args": args or None}


def ring(tid, lost=0, oldest_end=-5.0, cap=8192):
    return {"tid": tid, "thread": f"t{tid}", "written": cap + lost,
            "capacity": cap, "lost": lost, "oldest_end_s": T0 + oldest_end}


def ctx_of(spans, rings=None, **more):
    ctx = {"t0": T0, "seconds": SECONDS, "spans": spans,
           "ring_stats": rings if rings is not None else [ring(ENGINE),
                                                          ring(CALLBACK)],
           "notes": {}, "counters": {}, "max_batch": 16,
           "window_rows": [{"id": f"m{i}"} for i in range(10)]}
    ctx.update(more)
    return ctx


def read(name, ctx):
    return specs.load_reader(name).read(ctx)


# one step of a healthy loop: admission, then a session of two chunks
def healthy():
    return [
        span("engine.admission", 1.00, 40, step=7, admitted=1,
             queued_after=0),
        span("engine.admission.dispatch", 1.01, 20, step=7, wave=3,
             kind="ragged"),
        span("engine.session", 1.05, 650, step=7, slots=4, carried=3,
             chunks=2, variant="decode_resident_greedy"),
        span("engine.emit", 1.35, 2, tid=CALLBACK, step=7, chunk=0, live=4),
        span("engine.decode_chunk", 1.05, 302, tid=CALLBACK, rid="r1"),
        span("engine.emit", 1.65, 2, tid=CALLBACK, step=7, chunk=1, live=4),
    ]


LAPPED = [ring(ENGINE), ring(CALLBACK, lost=40, oldest_end=3.0)]
LAPPED_BEFORE = [ring(ENGINE), ring(CALLBACK, lost=40, oldest_end=-2.0)]
OTHER_THREAD_LAPPED = [ring(ENGINE), ring(CALLBACK), ring(99, lost=9000,
                                                          oldest_end=40.0)]


def first_tokens():
    # ten window messages, one warm-phase message that does not count
    out = [span("engine.first_token", 2.0 + i, 100.0 + 10 * i, tid=CALLBACK,
                rid=f"r{i}", step=3 + i, mid=f"m{i}", cached_tokens=64,
                new_tokens=40) for i in range(10)]
    out.append(span("engine.first_token", 0.5, 9000, tid=CALLBACK, rid="w",
                    step=1, mid="warm-1", cached_tokens=0, new_tokens=99))
    return out


@pytest.mark.parametrize("spans,rings,want", [
    (first_tokens(), None, 180.0),      # nearest rank: the 9th of 10
    (first_tokens(), LAPPED_BEFORE, 180.0),
    (first_tokens(), OTHER_THREAD_LAPPED, 180.0),
    (first_tokens(), LAPPED, None),
    (healthy(), None, None),            # a program without the span
    ([], None, None),
])
def test_first_token_after_admit_ms_p90(spans, rings, want):
    ctx = ctx_of(spans, rings)
    got = read("first_token_after_admit_ms_p90", ctx)
    assert got == (pytest.approx(want) if want is not None else None)
    if rings is LAPPED:
        assert "lapped" in ctx["notes"][
            "first_token_after_admit_ms_p90"]["unread"]


def sessions(*rows):
    return [span("engine.session", start, dur, step=step, slots=slots,
                 carried=carried, chunks=1)
            for start, dur, step, slots, carried in rows]


@pytest.mark.parametrize("spans,rings,want", [
    # 30 ms and 80 ms pauses between consecutive steps that carried slots
    # over; step 5 -> 7 skipped an idle step and 7 -> 8 carried nobody
    (sessions((1.0, 500, 3, 4, 0), (1.53, 400, 4, 5, 4),
              (2.01, 300, 5, 5, 5), (9.0, 300, 7, 2, 2),
              (9.5, 300, 8, 1, 0)), None, 80.0),
    (sessions((1.0, 500, 3, 4, 0), (1.53, 400, 4, 5, 4)),
     [ring(ENGINE, lost=1, oldest_end=0.0)], None),
    # the lapped ring holds no span of the engine: nothing is missing
    (sessions((1.0, 500, 3, 4, 0), (1.53, 400, 4, 5, 4)), LAPPED, 30.0),
    # a pause that began before the window is the warm phase's
    (sessions((-2.0, 500, 3, 4, 0), (-1.4, 400, 4, 5, 4)), None, None),
    (first_tokens(), None, None),
])
def test_decode_pause_ms_p90(spans, rings, want):
    ctx = ctx_of(spans, rings)
    got = read("decode_pause_ms_p90", ctx)
    assert got == (pytest.approx(want) if want is not None else None)


def stalled():
    # the dispatch of wave 9 takes 2.4 s (a compile) inside step 12's
    # admission; nothing of the engine ends in between
    return healthy() + [
        span("engine.admission", 5.00, 2450, step=12, admitted=1,
             queued_after=3),
        span("engine.admission.plan", 5.001, 1, step=12, rows=1,
             cached_tokens=0, new_tokens=80),
        span("engine.admission.dispatch", 5.01, 2400, step=12, wave=9,
             kind="ragged"),
        span("engine.wait", 20.0, 9000, step=13),
        # a request's own wait in the queue spans the stall and began
        # later than the round, but is no phase and names nothing
        span("engine.admit", 5.005, 2440, rid="r9", step=12),
    ]


@pytest.mark.parametrize("spans,rings,want,where", [
    # healthy: the admission's end to the session's first chunk out
    # (1.040 -> 1.352), a little over the chunk to chunk 300 ms
    (healthy(), None, 312.0, ("engine.session", 7)),
    (stalled(), None, 2408.0, ("engine.admission.dispatch", 12)),
    (stalled(), LAPPED, None, None),
    # idle: a wait of seconds with nothing open is no stall
    ([span("engine.wait", 1.0, 9000, step=2),
      span("engine.wait", 10.5, 9000, step=3)], None, None, None),
    ([], None, None, None),
])
def test_engine_stall_ms_max(spans, rings, want, where):
    ctx = ctx_of(spans, rings)
    got = read("engine_stall_ms_max", ctx)
    assert got == (pytest.approx(want) if want is not None else None)
    if where:
        note = ctx["notes"]["engine_stall_ms_max"]
        assert (note["span"], note["step"]) == where
        assert note["thread"] == "swarmdb-engine"


@pytest.mark.parametrize("name,counters,want", [
    ("prefill_tokens_per_wave",
     {"prefill_packed_tokens": 27000, "prefill_device_waves": 300}, 90.0),
    ("prefill_tokens_per_wave", {"prefill_packed_tokens": 27000}, None),
    ("decode_batch_fill",
     {"decode_slot_chunks": 1040, "engine_resident_chunks": 130}, 50.0),
    ("decode_batch_fill", {"engine_resident_chunks": 130}, None),
    ("decode_batch_fill", {"decode_slot_chunks": 0}, None),
    ("kv_reserved_written_share",
     {"kv_page_chunks_written": 300, "kv_page_chunks_reserved": 1200}, 25.0),
    ("kv_reserved_written_share", {"kv_page_chunks_written": 0}, None),
])
def test_counter_readers(name, counters, want):
    got = read(name, ctx_of([], counters=counters))
    assert got == (pytest.approx(want) if want is not None else None)
    assert got is None or isinstance(got, float)


def test_readers_take_the_programs_own_tracer_when_given_no_spans():
    # no ctx["spans"]: the reader imports TRACER; nothing of the window
    # is in it here, so there is nothing to read and nothing raises
    ctx = {"t0": 4e9, "seconds": 1.0, "notes": {}, "window_rows": []}
    for name in ("first_token_after_admit_ms_p90", "decode_pause_ms_p90",
                 "engine_stall_ms_max"):
        assert read(name, ctx) is None


def test_a_whole_tiny_run_reports_the_new_metrics(tmp_path):
    # run.py as it is, on the CPU, finds the readers by the names in the
    # spec and takes the spans and counters from the engine it ran
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--spec",
         str(SPEC), "--workload", "tiny.chat", "--platform", "cpu",
         "--seed", str(2 ** 31 + 11), "--seconds", "3", "--trace", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    metrics = out["metrics"]
    assert {"first_token_after_admit_ms_p90", "engine_stall_ms_max",
            "prefill_tokens_per_wave", "decode_batch_fill",
            "kv_reserved_written_share", "queue_wait_ms_p90"} <= set(metrics)
    # no device trace on the CPU, so no gap to name
    assert "idle_named_by_program_share" not in metrics
    for name in ("decode_batch_fill", "kv_reserved_written_share"):
        assert 0 < metrics[name]["value"] <= 100


def test_the_phases_spec_is_the_tiny_spec_and_seven_entries_more():
    tiny = json.loads((SPEC.parent / "spec.json").read_text())
    more = json.loads(SPEC.read_text())
    root = json.loads((ROOT / "BENCHMARK.json").read_text())
    n = len(tiny["per_layer"])
    assert more["per_layer"][:n] == tiny["per_layer"]
    assert {k: v for k, v in more.items() if k != "per_layer"} == {
        k: v for k, v in tiny.items() if k != "per_layer"}
    added = more["per_layer"][n:]
    assert len(added) == 7
    # the same entries as BENCHMARK.json's, but for its lists of cells
    listed = {m["name"]: {k: v for k, v in m.items() if k != "workloads"}
              for m in root["per_layer"]}
    assert [listed[m["name"]] for m in added] == added
