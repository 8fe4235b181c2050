"""The process's watcher (``swarmdb_tpu/obs/procwatch.py``): the verdict
as a pure function, the sampler on hand-made ``/proc`` texts, and the
watcher itself against stalls that the test provokes: a thread that keeps
the interpreter, a process that is stopped whole, an engine whose beat is
held back. Nothing here sleeps more than a second."""

import gc
import json
import logging
import os
import re
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from swarmdb_tpu.obs import TRACER, FlightRecorder, procwatch  # noqa: E402
from swarmdb_tpu.utils.metrics import MetricsRegistry  # noqa: E402

WATCHER = "swarmdb-procwatch"
# a sandboxed kernel (gVisor, the chip's machine) keeps no scheduler
# accounts: what rests on them is not asserted there
SCHEDSTAT = os.path.exists("/proc/thread-self/schedstat")


def spans(name):
    return [e for e in TRACER.snapshot()
            if e["cat"] == "process" and e["name"] == name]


@pytest.fixture
def watch(monkeypatch):
    """A watcher of this process, fast enough on the draw for stalls of
    a few tenths of a second; released, and found gone, afterwards."""
    monkeypatch.setattr(TRACER, "enabled", True)
    monkeypatch.setattr(procwatch, "ENGINE_LATE_S", 0.25)
    TRACER.reset()
    metrics = MetricsRegistry()
    w = procwatch.acquire(metrics, [])
    assert w is not None and w.metrics is metrics
    yield w
    procwatch.release(w.engines)
    assert procwatch._WATCH is None
    assert WATCHER not in {t.name for t in threading.enumerate()}


# ------------------------------------------------------------- the verdict

ACCOUNTS = dict(run_ms=0.5, runq_ms=0.5, proc_cpu_ms=0.0, majflt=0,
                blkio_ms=0.0, psi_mem_ms=0.0, psi_io_ms=0.0)


@pytest.mark.parametrize("changes, verdict", [
    (dict(proc_cpu_ms=1990.0), "interpreter_held"),
    (dict(proc_cpu_ms=1000.0), "interpreter_held"),     # half of it burned
    (dict(proc_cpu_ms=999.0), "unknown"),
    (dict(runq_ms=1900.0, proc_cpu_ms=3000.0), "starved"),
    # the boundary: queued for half of it is starved, whatever burned
    (dict(runq_ms=1000.0, proc_cpu_ms=2000.0), "starved"),
    (dict(runq_ms=999.0, proc_cpu_ms=2000.0), "interpreter_held"),
    (dict(majflt=3), "blocked_memory"),
    (dict(psi_mem_ms=400.0), "blocked_memory"),
    (dict(blkio_ms=30.0), "blocked_io"),
    (dict(psi_io_ms=250.0), "blocked_io"),
    (dict(psi_io_ms=150.0), "frozen"),      # pressure elsewhere, a tenth
    (dict(), "frozen"),
    (dict(proc_cpu_ms=400.0), "unknown"),   # somebody ran, not for half
    (dict(run_ms=1500.0, proc_cpu_ms=1500.0), "unknown"),   # the watcher
    (dict(throttled_ms=1200.0), "starved"),     # the cgroup's quota
    (dict(throttled_ms=900.0), "frozen"),
])
def test_classify(changes, verdict):
    assert procwatch.classify(dict(ACCOUNTS, ms=2000.0, **changes)) == verdict


@pytest.mark.parametrize("accounts, verdict", [
    # a kernel without schedstat: the thread's CPU clock, no runq_ms
    (dict(run_ms=0.4, proc_cpu_ms=1990.0), "interpreter_held"),
    (dict(run_ms=0.4, proc_cpu_ms=10.0, majflt=0, blkio_ms=0.0), "frozen"),
    (dict(run_ms=0.4, proc_cpu_ms=10.0, throttled_ms=1900.0), "starved"),
    (dict(run_ms=0.4, proc_cpu_ms=600.0), "unknown"),
    (dict(proc_cpu_ms=1990.0), "unknown"),      # no account of its own
    (dict(run_ms=0.4), "unknown"),
])
def test_classify_with_accounts_left_out(accounts, verdict):
    assert procwatch.classify(dict(accounts, ms=2000.0)) == verdict


# ------------------------------------------------------------- the sampler

STAT = ("4242 (python3 (a b)) S 1 4242 4242 0 -1 4194560 9000 0 17 0 "
        "1500 250 0 0 20 0 31 0 100 1000000 5000 18446744073709551615 "
        "1 1 0 0 0 0 0 0 0 0 0 0 17 3 0 0 45 0 0 0 0 0 0 0 0 0 0\n")


def test_the_parsers_on_hand_made_texts():
    assert procwatch.parse_schedstat("215429000 122841000 7\n") == {
        "run_ms": 215.429, "runq_ms": 122.841}
    tick_ms = 1e3 / os.sysconf("SC_CLK_TCK")
    assert procwatch.parse_stat(STAT) == {"majflt": 17,
                                          "blkio_ms": 45 * tick_ms}
    assert procwatch.parse_cpu_stat(
        "usage_usec 10\nnr_throttled 4\nthrottled_usec 2500\n") == {
            "throttled_ms": 2.5}
    assert procwatch.parse_cpu_stat(
        "nr_periods 9\nnr_throttled 4\nthrottled_time 2500000\n") == {
            "throttled_ms": 2.5}
    assert procwatch.parse_cpu_stat("nr_periods 9\n") == {}
    assert procwatch.parse_pressure(
        "some avg10=0.60 avg60=0.64 avg300=0.98 total=15701258\n"
        "full avg10=0.00 avg60=0.00 avg300=0.00 total=0\n", "psi_cpu_ms") == {
            "psi_cpu_ms": 15701.258}
    assert procwatch.parse_steal(
        "cpu  1722 0 1015 647773 1269 0 19 7 0 0\ncpu0 1 2 3\n") == {
            "steal_ms": 7 * tick_ms}
    assert procwatch.parse_steal("cpu  1 2 3\n") == {}


def test_the_sampler_leaves_out_what_is_not_there(tmp_path):
    (tmp_path / "own").write_text("1000000 2000000 3\n")
    (tmp_path / "stat").write_text(STAT)
    (tmp_path / "pressure_io").write_text("not what the kernel writes\n")
    (tmp_path / "cpu.stat").write_text("throttled_usec 9000\n")
    task = tmp_path / "task" / "77"
    task.mkdir(parents=True)
    (task / "schedstat").write_text("5000000 250000 9\n")
    acc = procwatch.Accounts({
        "own": str(tmp_path / "own"), "stat": str(tmp_path / "stat"),
        "cgroup": [str(tmp_path / "nowhere"), str(tmp_path / "cpu.stat")],
        "psi_cpu": str(tmp_path / "no_such_file"),
        "psi_io": str(tmp_path / "pressure_io"),
        "machine": str(tmp_path / "missing"),
        "tasks": str(tmp_path / "task")})
    try:
        got = acc.read()
        assert set(got) == {"run_ms", "runq_ms", "majflt", "blkio_ms",
                            "throttled_ms"}
        assert got["run_ms"] == 1.0 and got["throttled_ms"] == 9.0
        # a running total is read anew from the same descriptor
        (tmp_path / "own").write_text("4000000 2000000 4\n")
        assert acc.read()["run_ms"] == 4.0
        assert acc.threads([77, None, 78]) == {
            77: {"run_ms": 5.0, "runq_ms": 0.25}}
        assert acc.threads([]) == {} and not acc._threads
    finally:
        acc.close()
    assert procwatch.Accounts({}).read() == {}


def test_stacks_name_threads_and_put_the_ones_that_wait_last():
    gate = threading.Event()

    def parked():
        gate.wait(5)

    th = threading.Thread(target=parked, name="parked")
    th.start()
    try:
        got = procwatch.stacks(3)
        assert "MainThread" not in got      # the caller's own is left out
        assert got["parked"].startswith("threading.py:")
        assert got["parked"].count(" < ") == 2 and "parked" in got["parked"]
        only = procwatch.stacks(1, lambda ident, name: name == "parked")
        assert list(only) == ["parked"] and " < " not in only["parked"]
    finally:
        gate.set()
        th.join(timeout=5)
    text = procwatch.stacks_text({
        "sleeper": "threading.py:355 wait < app.py:9 serve",
        "holder": "__init__.py:167 match < hold.py:3 hold"})
    assert text.splitlines() == [
        "holder: __init__.py:167 match < hold.py:3 hold",
        "sleeper: threading.py:355 wait < app.py:9 serve"]
    assert len(procwatch.stacks_text({"t": "x.py:1 f" * 40}, limit=40)) == 40


# ------------------------------------------------------ provoked stalls

def backtracking(seconds):
    """A text on which ``(a+)+$`` backtracks for about ``seconds``, in
    one C call that keeps the interpreter."""
    n = 16
    while True:
        t = time.perf_counter()
        re.match(r"(a+)+$", "a" * n + "b")
        took = time.perf_counter() - t
        if took * 2 >= seconds:
            return "a" * (n + 1) + "b"
        n += 1


def test_a_thread_that_keeps_the_interpreter_is_a_stall_with_its_frame(
        watch, caplog):
    """What is asserted is order and accounts, none of it a window of
    the wall clock: on a loaded machine the call is stretched by the
    neighbours' turns and every thread wakes late."""
    text = backtracking(0.4)
    held = {}
    seen = threading.Event()

    def hold():
        # begin as the watcher goes to sleep on a new deadline: awake, it
        # would take the call into its own turn and see no late wake
        due = watch.due_ns
        while watch.due_ns == due:
            time.sleep(0.001)
        held["t0"], cpu = time.time(), time.thread_time()
        re.match(r"(a+)+$", text)
        held["cpu_ms"] = (time.thread_time() - cpu) * 1e3
        held["t1"] = time.time()
        while not seen.is_set():    # alive, and here, while the watcher
            time.sleep(0.01)        # names the dump's threads

    def settled():
        """The stalls that meet the call, once the watcher is through
        with them (the counters are the last thing a stall writes)."""
        stalls = spans("process.stall")
        c = watch.metrics.counters
        if ("t1" not in held or c["process_stalls"].value != len(stalls)
                or c["process_stall_us"].value
                < sum(e["args"]["ms"] for e in stalls) * 1e3 - len(stalls)):
            return []
        return [e for e in stalls if e["start_s"] < held["t1"]
                and e["start_s"] + e["dur_us"] * 1e-6 > held["t0"]]

    th = threading.Thread(target=hold, name="holder")
    th.start()
    deadline = time.monotonic() + 10
    while not (stalls := settled()) and time.monotonic() < deadline:
        time.sleep(0.01)
    seen.set()
    th.join(timeout=10)
    assert not th.is_alive()
    assert stalls, spans("process.stall")
    stall = max(stalls, key=lambda e: e["dur_us"])
    args = stall["args"]
    # it covers the call: the watcher slept to a deadline a tick or less
    # after the call began (whatever the machine does meanwhile, the
    # deadline was set before), and woke when the call had ended, so the
    # stall is no shorter than the CPU the call took, less that tick
    assert stall["start_s"] <= held["t0"] + procwatch.TICK_S + 0.005
    assert args["ms"] >= held["cpu_ms"] - procwatch.TICK_S * 1e3 - 5
    assert args["ms"] >= procwatch.STALL_S * 1e3
    # the accounts, not the verdict: a loaded machine may read starved.
    # The process burned at least what the call did
    assert args["proc_cpu_ms"] >= held["cpu_ms"] * 0.9
    assert args["verdict"] in ("interpreter_held", "starved", "unknown")
    # the watcher holds the interpreter when it looks: the holder stands
    # where it gave it up, in the function that made the call
    holder = [ln for ln in args["stacks"].splitlines()
              if ln.startswith("holder: ")]
    assert holder and " hold" in holder[0]
    c = watch.metrics.counters
    assert c["process_stall_us"].value >= args["ms"] * 1e3 - 1
    # the samples hold the stall as it fell
    assert max(e["args"]["late_ms_max"] for e in spans("process.sample")) \
        >= args["ms"] - 1
    assert c["process_wake_late_us"].value >= args["ms"] * 1e3 - 1e3
    # and the reader answers from the newest stall: all of it, asked for
    # a stretch that holds it
    t0, t1 = watch.last_stall
    assert t1 - t0 >= procwatch.STALL_S * 1e9
    age = (time.monotonic_ns() - t0) / 1e9 + 0.01
    assert procwatch.process_late_s(age) == \
        pytest.approx((t1 - t0) / 1e9, abs=1e-3)


def test_a_long_stall_goes_to_the_logger(watch, caplog, monkeypatch):
    monkeypatch.setattr(procwatch, "WARN_S", 0.2)
    text = backtracking(0.3)
    with caplog.at_level(logging.WARNING, logger=procwatch.logger.name):
        time.sleep(0.1)
        re.match(r"(a+)+$", text)
        time.sleep(0.1)
    lines = [r.getMessage() for r in caplog.records
             if r.name == procwatch.logger.name]
    assert any(line.startswith("process stood still 0.")
               and "proc_cpu_ms=" in line for line in lines), lines


def test_without_the_kernels_accounts_the_clocks_and_the_stacks_are_left(
        monkeypatch):
    """The chip's machine (PR 39): no ``schedstat``, no pressure files, no
    cgroup account. What is left still tells a held interpreter."""
    accounts = procwatch.Accounts
    monkeypatch.setattr(procwatch, "Accounts", lambda: accounts({}))
    monkeypatch.setattr(TRACER, "enabled", True)
    TRACER.reset()
    text = backtracking(0.4)
    metrics = MetricsRegistry()
    eng = FakeEngine()
    procwatch.acquire(metrics, [eng])
    try:
        # a call that the neighbours' turns stretch to twice its CPU reads
        # `unknown`, rightly (the process burned under half of the
        # stall): such a call told nothing, and another is made
        for _attempt in range(4):
            TRACER.reset()
            time.sleep(0.3)
            wall, cpu = time.perf_counter(), time.thread_time()
            re.match(r"(a+)+$", text)
            cpu = time.thread_time() - cpu
            wall = time.perf_counter() - wall
            time.sleep(0.3)
            if cpu >= 0.7 * wall:
                break
    finally:
        procwatch.release([eng])
        eng.hold.set()
    stall = max(spans("process.stall"), key=lambda e: e["dur_us"])
    args = stall["args"]
    assert "runq_ms" not in args and "majflt" not in args
    assert args["run_ms"] < 50 and args["proc_cpu_ms"] >= cpu * 1e3 * 0.9
    assert args["verdict"] == "interpreter_held"
    assert "MainThread: " in args["stacks"]     # it made the call
    sample = spans("process.sample")[1]["args"]
    assert {"ticks", "late_ms_max", "run_ms", "proc_cpu_ms", "gc_ms"} <= set(
        sample)
    assert "engine_threads" not in sample
    assert "engine_thread_watch_us" not in metrics.counters
    assert metrics.counters["process_watch_ticks"].value > 10


CHILD = """
import json, sys, time
sys.path.insert(0, {root!r})
from swarmdb_tpu.obs import TRACER, procwatch
from swarmdb_tpu.utils.metrics import MetricsRegistry
w = procwatch.acquire(MetricsRegistry(), [])
time.sleep(0.3)
print("ready", flush=True)
time.sleep(1.0)
procwatch.release([])
print(json.dumps([e for e in TRACER.snapshot()
                  if e["name"] == "process.stall"]), flush=True)
"""


def test_a_stopped_process_reads_no_cpu_and_no_queue():
    child = subprocess.Popen(
        [sys.executable, "-c", CHILD.format(root=str(ROOT))],
        stdout=subprocess.PIPE, text=True,
        env=dict(os.environ, SWARMDB_TRACE="1"))
    try:
        assert child.stdout.readline().strip() == "ready"
        time.sleep(0.1)
        child.send_signal(signal.SIGSTOP)
        time.sleep(0.5)
        child.send_signal(signal.SIGCONT)
        out, _ = child.communicate(timeout=20)
    finally:
        child.kill()
    stalls = [e for e in json.loads(out.strip().splitlines()[-1])
              if e["args"]["ms"] >= 400]
    assert len(stalls) == 1, out
    args = stalls[0]["args"]
    # a loaded machine may send the second signal late, never early
    assert args["ms"] <= 1500, args
    assert args["proc_cpu_ms"] < args["ms"] / 10, args
    assert args["run_ms"] < args["ms"] / 10, args
    assert args.get("runq_ms", 0) < args["ms"] / 10, args
    # nobody ran and nobody queued: stopped, or held by the kernel
    assert args["verdict"] in ("frozen", "blocked_memory",
                               "blocked_io"), args


class FakeEngine:
    """What the watcher takes of an engine: a loop thread with the
    kernel's id, a beat that this test holds back, ``_in_step``."""

    def __init__(self):
        self.beat = time.monotonic()
        self.hold = threading.Event()
        self._in_step = True
        self._native_id = None
        self._thread = threading.Thread(target=self.loop_body, daemon=True,
                                        name="swarmdb-engine")
        self._thread.start()

    def loop_body(self):
        self._native_id = threading.get_native_id()
        while not self.hold.wait(0.01):
            self.beat = time.monotonic()
        self.in_the_device()

    def in_the_device(self):
        self.release = threading.Event()
        self.release.wait(5)

    def beat_age_s(self, now=0.0):
        return (now or time.monotonic()) - self.beat


def test_an_engine_that_is_late_alone_gives_its_frames(watch):
    eng = FakeEngine()
    watch.engines = [eng]
    time.sleep(0.25)
    assert not spans("process.engine_late")
    eng.hold.set()
    time.sleep(0.6)
    late = spans("process.engine_late")
    assert len(late) == 1, late        # once a stall, not once a sample
    args = late[0]["args"]
    assert args["in_step"] is True and args["lane"] == 0
    assert 250 <= args["beat_age_ms"] <= 600
    assert late[0]["dur_us"] == pytest.approx(args["beat_age_ms"] * 1e3,
                                              rel=0.05)
    assert "in_the_device" in args["frames"]["swarmdb-engine"]
    assert "loop_body" in args["frames"]["swarmdb-engine"]
    assert "proc_cpu_ms" in args and args["over_ms"] > 0
    if SCHEDSTAT:
        # the loop thread's own account, and the time it was watched
        assert any(e["args"].get("engine_threads") == 1
                   for e in spans("process.sample"))
        c = watch.metrics.counters
        assert c["engine_thread_watch_us"].value > 0.3e6
        assert c["engine_thread_run_us"].value >= 0
        assert c["engine_thread_runq_wait_us"].value >= 0
    eng.release.set()
    eng._thread.join(timeout=5)
    assert not eng._thread.is_alive()


def test_the_process_standing_still_is_not_the_engine_being_late(watch):
    eng = FakeEngine()
    watch.engines = [eng]
    text = backtracking(0.5)
    time.sleep(0.2)
    re.match(r"(a+)+$", text)       # the engine's beat ages with it
    time.sleep(0.2)
    assert spans("process.stall") and not spans("process.engine_late")
    eng.hold.set()
    eng.release = threading.Event()
    eng.release.set()


def test_stalled_within_takes_the_part_that_overlaps():
    w = procwatch.ProcWatch(MetricsRegistry())
    now = 100 * 10 ** 9
    w.last_stall = (now - 8 * 10 ** 9, now - 10 ** 9)
    assert w.stalled_within(7.5, now) == pytest.approx(6.5)
    assert w.stalled_within(0.5, now) == 0.0
    assert w.stalled_within(20.0, now) == pytest.approx(7.0)
    # a caller that woke before the watcher did: the stall is not
    # recorded yet, its deadline is
    w.due_ns = now - 3 * 10 ** 9
    assert w.stalled_within(0.5, now) == pytest.approx(0.5)
    assert w.stalled_within(20.0, now) == pytest.approx(7.0)
    w.last_stall = (0, 0)
    assert w.stalled_within(20.0, now) == pytest.approx(3.0)


# ---------------------------------------------------------- the lifecycle

def test_start_and_stop_are_counted_and_leave_nothing_behind(monkeypatch):
    monkeypatch.setattr(TRACER, "enabled", True)
    callbacks = list(gc.callbacks)
    a, b = MetricsRegistry(), MetricsRegistry()
    e1, e2 = object(), object()
    w1 = procwatch.acquire(a, [e1])
    try:
        w2 = procwatch.acquire(b, [e2, e1])
        assert w1 is w2 and w1.metrics is a and w1.engines == [e1, e2]
        assert [t.name for t in threading.enumerate()].count(WATCHER) == 1
        assert len(gc.callbacks) == len(callbacks) + 1
        time.sleep(0.3)
        procwatch.release([e2])
        assert procwatch._WATCH is w1 and w1.engines == [e1]
        assert WATCHER in {t.name for t in threading.enumerate()}
    finally:
        procwatch.release([e1])
    assert procwatch._WATCH is None
    assert WATCHER not in {t.name for t in threading.enumerate()}
    assert gc.callbacks == callbacks
    assert a.counters["process_watch_ticks"].value >= 5
    assert not b.counters
    procwatch.release([])       # one release too many stops nothing twice


def test_with_the_tracer_off_nothing_starts(monkeypatch):
    monkeypatch.setattr(TRACER, "enabled", False)
    callbacks = list(gc.callbacks)
    assert procwatch.acquire(MetricsRegistry(), []) is None
    assert procwatch._WATCH is None and procwatch.process_late_s(9.0) == 0.0
    assert WATCHER not in {t.name for t in threading.enumerate()}
    assert gc.callbacks == callbacks


def test_the_environment_turns_it_off():
    code = ("import threading; from swarmdb_tpu.obs import procwatch; "
            "from swarmdb_tpu.utils.metrics import MetricsRegistry; "
            "print(procwatch.acquire(MetricsRegistry(), []), "
            "[t.name for t in threading.enumerate()])")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=ROOT, env=dict(os.environ, SWARMDB_TRACE="0"), timeout=60)
    assert out.stdout.strip() == "None ['MainThread']", out.stderr[-2000:]


# -------------------------------------------------------- the supervisor

def test_the_supervisor_says_how_much_of_a_beats_age_was_the_process(
        monkeypatch, caplog):
    from swarmdb_tpu.backend import supervisor as sup

    class Lane:
        metrics = MetricsRegistry()
        flight = FlightRecorder()
        _thread = None

        def beat_age_s(self, now=0.0):
            return 7.553

        def alive(self):
            return True

    class Watch:
        def stalled_within(self, age_s, now=0):
            return age_s - 0.153

    lane = Lane()
    s = sup.LaneSupervisor(lane, poll_s=10.0)
    TRACER.reset()
    for watch, late in ((Watch(), 7.4), (None, 0.0)):
        monkeypatch.setattr(procwatch, "_WATCH", watch)
        with caplog.at_level(logging.WARNING, logger=sup.logger.name):
            s._transition(0, lane, s.health[0], sup.LaneState.SUSPECT)
        assert (f"beat age 7.553s, of which the process stood still "
                f"{late:.3f}s") in caplog.records[-1].getMessage()
        event = lane.flight.events()[-1]
        assert event["kind"] == "lane.suspect"
        assert event["beat_age_s"] == 7.553
        assert event["process_late_s"] == pytest.approx(late)
        mark = [e for e in TRACER.snapshot()
                if e["name"] == "lane.suspect"][-1]
        assert mark["args"] == {"lane": 0,
                                "process_late_s": pytest.approx(late)}
    # no verdict of the supervisor's depends on it
    assert s.health[0].state == sup.LaneState.SUSPECT
