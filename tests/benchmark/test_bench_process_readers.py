"""The three readers of layer ``process`` (PR 39), each on hand-made
spans, rings and counters: what it reads, what it leaves out, and ``None``
where the program has no watcher or its ring was lapped inside the window.
And one whole tiny ``run.py`` on the CPU, as it is, that reports all
three."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import spec as specs  # noqa: E402

SPEC = ROOT / "tests" / "benchmark" / "tiny" / "spec-process.json"
T0, SECONDS = 1000.0, 50.0
WATCHER, ENGINE = 21, 11            # thread ids


def span(name, start, dur_ms, tid=WATCHER, cat="process", **args):
    return {"name": name, "cat": cat, "rid": None, "start_s": T0 + start,
            "dur_us": dur_ms * 1e3, "tid": tid,
            "thread": "swarmdb-procwatch" if tid == WATCHER else "engine",
            "args": args or None}


def ring(tid, lost=0, oldest_end=-5.0, cap=8192):
    return {"tid": tid, "thread": f"t{tid}", "written": cap + lost,
            "capacity": cap, "lost": lost, "oldest_end_s": T0 + oldest_end}


def ctx_of(spans, rings=None, **more):
    ctx = {"t0": T0, "seconds": SECONDS, "spans": spans,
           "ring_stats": rings if rings is not None else [ring(WATCHER),
                                                          ring(ENGINE)],
           "notes": {}, "counters": {}}
    ctx.update(more)
    return ctx


def read(name, ctx):
    return specs.load_reader(name).read(ctx)


def samples(late, start=0.0):
    """One ``process.sample`` a tenth of a second, from ``start``."""
    return [span("process.sample", start + 0.1 * i, 100, ticks=5,
                 late_ms_max=v, late_ms_sum=v + 0.2, run_ms=0.3)
            for i, v in enumerate(late)]


def stall(start, ms, verdict="interpreter_held", **args):
    return span("process.stall", start, ms, ms=ms, verdict=verdict,
                proc_cpu_ms=ms - 5.0, run_ms=0.2, runq_ms=0.1,
                beat_age_s=[ms / 1e3], **args)


# ------------------------------------------------- process_stall_ms_max

def test_stall_is_zero_where_the_watcher_ran_and_wrote_none():
    ctx = ctx_of(samples([0.1] * 20))
    assert read("process_stall_ms_max", ctx) == 0.0
    assert isinstance(read("process_stall_ms_max", ctx), float)
    assert "process_stall_ms_max" not in ctx["notes"]


def test_stall_takes_the_longest_that_overlaps_the_window():
    spans = samples([0.1] * 5) + [
        stall(-9.0, 7000.0),                      # before the window
        stall(12.0, 2054.7, stacks="holder: json.py:1 dumps\nx: y",
              stacks_path="/tmp/s.txt"),
        stall(30.0, 340.0, verdict="starved"),
        stall(52.0, 9000.0)]                      # in the drain
    ctx = ctx_of(spans)
    assert read("process_stall_ms_max", ctx) == pytest.approx(2054.7)
    note = ctx["notes"]["process_stall_ms_max"]
    assert note["verdict"] == "interpreter_held" and note["stalls"] == 2
    assert note["at_s"] == pytest.approx(12.0)
    assert note["accounts"]["proc_cpu_ms"] == pytest.approx(2049.7)
    assert note["beat_age_s"] == [pytest.approx(2.0547)]
    assert note["stacks"].startswith("holder: json.py:1 dumps")


def test_an_engine_that_was_late_alone_is_kept_in_the_notes():
    late = span("process.engine_late", 15.0, 2054.7, lane=0, in_step=True,
                beat_age_ms=1003.2,
                frames={"swarmdb-engine": "array.py:1 block_until_ready"})
    ctx = ctx_of(samples([0.1] * 5) + [
        late, span("process.engine_late", 60.0, 1500.0, lane=0)])
    assert read("process_stall_ms_max", ctx) == 0.0
    note, = ctx["notes"]["process_engine_late"]
    assert note["at_s"] == pytest.approx(15.0) and note["in_step"] is True
    assert "block_until_ready" in note["frames"]["swarmdb-engine"]


@pytest.mark.parametrize("start, ms", [(-1.5, 4000.0), (48.5, 4000.0)])
def test_a_stall_astride_the_windows_edge_counts_whole(start, ms):
    ctx = ctx_of(samples([0.1] * 5) + [stall(start, ms, verdict="frozen"),
                                       stall(20.0, 900.0)])
    assert read("process_stall_ms_max", ctx) == pytest.approx(ms)
    assert ctx["notes"]["process_stall_ms_max"]["at_s"] == \
        pytest.approx(start)
    assert ctx["notes"]["process_stall_ms_max"]["verdict"] == "frozen"


# ----------------------------------------------- watch_wake_late_ms_p90

def test_wake_lateness_is_the_windows_samples_p90_stalls_among_them():
    late = [0.1] * 17 + [3.0, 6.0, 2054.7]
    spans = (samples([99.0] * 5, start=-1.0)      # warm phase: not counted
             + samples(late, start=1.0)
             + samples([77.0] * 3, start=50.5))   # after the window
    assert read("watch_wake_late_ms_p90", ctx_of(spans)) == 3.0
    assert read("watch_wake_late_ms_p90",
                ctx_of(samples([0.2] * 10))) == 0.2


def test_stalls_alone_give_no_lateness():
    assert read("watch_wake_late_ms_p90",
                ctx_of([stall(3.0, 500.0)])) is None


# --------------------------------- none to read, or part of it gone

@pytest.mark.parametrize("name", ["process_stall_ms_max",
                                  "watch_wake_late_ms_p90"])
def test_a_program_without_the_watcher_gives_none(name):
    # the parent commit: engine spans, none of category process
    engine = [span("engine.session", 1.0, 650, tid=ENGINE, cat="engine",
                   step=7)]
    ctx = ctx_of(engine)
    assert read(name, ctx) is None and not ctx["notes"]
    assert read(name, ctx_of([])) is None


@pytest.mark.parametrize("name", ["process_stall_ms_max",
                                  "watch_wake_late_ms_p90"])
def test_a_lapped_watcher_ring_gives_none_and_a_note(name):
    spans = samples([0.1] * 20, start=10.0) + [stall(15.0, 800.0)]
    lapped = [ring(WATCHER, lost=300, oldest_end=10.0), ring(ENGINE)]
    ctx = ctx_of(spans, lapped)
    assert read(name, ctx) is None
    assert "lapped inside the window" in ctx["notes"][name]["unread"]
    # a ring that lapped before the window began, or another thread's
    for rings in ([ring(WATCHER, lost=300, oldest_end=-2.0), ring(ENGINE)],
                  [ring(WATCHER), ring(ENGINE, lost=300, oldest_end=10.0)]):
        assert read(name, ctx_of(spans, rings)) is not None


# ---------------------------------------- engine_thread_runq_wait_share

def test_runq_wait_share_of_one_and_of_two_engine_threads():
    one = {"engine_thread_runq_wait_us": 250_000,
           "engine_thread_run_us": 30_000_000,
           "engine_thread_watch_us": 50_000_000}
    assert read("engine_thread_runq_wait_share",
                ctx_of([], counters=one)) == pytest.approx(0.5)
    # two lanes watched for the whole window: twice its length
    two = dict(one, engine_thread_watch_us=100_000_000)
    assert read("engine_thread_runq_wait_share",
                ctx_of([], counters=two)) == pytest.approx(0.25)
    assert read("engine_thread_runq_wait_share", ctx_of([], counters=dict(
        one, engine_thread_runq_wait_us=0))) == 0.0


def test_runq_wait_share_is_none_without_the_counters():
    assert read("engine_thread_runq_wait_share", ctx_of([])) is None
    assert read("engine_thread_runq_wait_share", ctx_of([], counters={
        "engine_resident_chunks": 9})) is None
    # a watcher that found no engine thread to read (off Linux)
    assert read("engine_thread_runq_wait_share", ctx_of([], counters={
        "process_watch_ticks": 2500, "engine_thread_watch_us": 0})) is None


# ------------------------------------------------------- a whole tiny run

def test_a_whole_tiny_run_reports_the_three(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"),
               TMPDIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    env.pop("SWARMDB_TRACE", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--spec",
         str(SPEC), "--workload", "tiny.chat", "--platform", "cpu",
         "--seed", str(2 ** 31 + 39), "--seconds", "3", "--trace", "1"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    out, facts = json.loads(lines[-1]), json.loads(lines[-2])
    assert out["correct"] is True and out["failed"] == 0
    m = out["metrics"]
    assert {"process_stall_ms_max", "watch_wake_late_ms_p90"} <= set(m)
    assert m["process_stall_ms_max"]["value"] >= 0.0
    assert m["watch_wake_late_ms_p90"]["value"] > 0.0
    c = facts["counters_window"]
    assert c["process_watch_ticks"] >= 100      # 50 a second, 3 seconds
    assert 0 < c["process_watch_awake_us"] < 3e6
    # a sandboxed kernel keeps no scheduler accounts (the chip's machine)
    if os.path.exists("/proc/thread-self/schedstat"):
        assert 0.0 <= m["engine_thread_runq_wait_share"]["value"] <= 100.0
        # one engine thread, watched for the window
        assert c["engine_thread_watch_us"] == pytest.approx(3e6, rel=0.1)
        assert c["engine_thread_run_us"] > 0
    if m["process_stall_ms_max"]["value"]:
        assert "verdict" in facts["notes"]["process_stall_ms_max"]
