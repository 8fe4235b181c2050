"""The process watches itself from outside the interpreter's own view.

Every other instrument of the program (the tracer's phases, the
supervisor's beat ages, the load generator's lateness) lives inside the
interpreter and stops with it, so none of them can tell the four things a
process-wide stall can be apart: one thread kept the interpreter, the
process was runnable and got no CPU, it was blocked in the kernel, or it
was stopped whole. This module adds one daemon thread a process,
``swarmdb-procwatch``, started and stopped (counted) by
``ServingService.start()`` / ``.stop()``, which

- sleeps to absolute deadlines ``TICK_S`` apart and takes, on every wake,
  ``late = now - due``: the wait for a CPU plus the wait for the
  interpreter, which every thread on a message's path pays at each hop;
- reads, every ``SAMPLE_TICKS`` ticks, what the kernel counts whether or
  not the interpreter runs (``Accounts``; the process's CPU clock every
  tick, which costs a third of a microsecond, so that a stall's own CPU
  does not hold the stretch before it), and writes the changes as one
  span ``process.sample`` and into the counters ``process_*`` /
  ``engine_thread_*`` of the service's registry;
- on a wake that is ``STALL_S`` late writes one span ``process.stall``
  (from ``due`` to now) with the changes across it, a verdict from
  ``classify`` and every thread's innermost frames at that wake: the
  watcher then holds the interpreter, so each thread stands where it
  gave the interpreter up, and the one that kept it stands at the call
  that kept it, or just behind it. (``faulthandler.dump_traceback_later``
  would read the stacks *during* the stall, from a C thread that needs
  no interpreter, and was built first: it walks other threads' frames
  without the GIL, and with the engine's and the runtime's threads
  running it crashed the process, 4 of 4 runs with the timer short and
  one worker of the whole test run with it at 1 s. PR 39.)
- when it is itself on time and an engine's beat is ``ENGINE_LATE_S`` old,
  writes one span ``process.engine_late`` with the frames of the engine's
  loop thread and of the callback threads.

Spans go to ``TRACER``'s ring of the watcher's own thread (``cat=
"process"``), so ``SWARMDB_TRACE=0`` turns the watcher off with every
other span: it then does not start. No annotation is ever held open
across the watcher's sleep: a stall is put on the profiler's clock by one
``TraceAnnotation`` a microsecond long, at detection. No JAX, no engine
import; a file that is not there leaves its fields out (off Linux the
lateness and the process's CPU time are what is left).
"""

from __future__ import annotations

import collections
import gc
import logging
import os
import sys
import threading
import time
from typing import Any, Deque, Dict, Iterable, List, Optional, Tuple

from .tracer import TRACER

__all__ = ["Accounts", "ProcWatch", "acquire", "classify", "process_late_s",
           "release"]

logger = logging.getLogger(__name__)

TICK_S = 0.02           # the watcher's deadlines
SAMPLE_TICKS = 5        # ticks a ``process.sample``
STALL_S = 0.1           # a wake this late is a ``process.stall``
WARN_S = 1.0            # a stall this long also goes to the logger
ENGINE_LATE_S = 1.0     # a beat this old, the watcher on time
STACKS_MAX = 2048       # bytes of ``stacks`` in a span
HISTORY = 16            # samples kept for ``process.engine_late``'s accounts

# innermost frames in these files are a thread that waits, not one that
# keeps the interpreter: they go last where ``stacks`` has to be cut
_WAITS = ("threading.py", "queue.py", "selectors.py")

_CLK_TCK = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100


# ------------------------------------------------------------ the accounts

def parse_schedstat(text: str) -> Dict[str, float]:
    """``/proc/<pid>/task/<tid>/schedstat``: nanoseconds on a CPU,
    nanoseconds runnable and waiting for one, slices."""
    run, wait = text.split()[:2]
    return {"run_ms": int(run) / 1e6, "runq_ms": int(wait) / 1e6}


def parse_stat(text: str) -> Dict[str, float]:
    """``/proc/self/stat``: major faults (field 12) and the block I/O
    delay (field 42, ticks). The command may hold spaces: fields are
    counted from the last ``)``."""
    f = text[text.rindex(")") + 2:].split()
    return {"majflt": int(f[9]), "blkio_ms": int(f[39]) * 1e3 / _CLK_TCK}


def parse_cpu_stat(text: str) -> Dict[str, float]:
    """A cgroup's ``cpu.stat``: ``throttled_usec`` (v2) or
    ``throttled_time`` in nanoseconds (v1)."""
    for line in text.splitlines():
        key, _, value = line.partition(" ")
        if key == "throttled_usec":
            return {"throttled_ms": int(value) / 1e3}
        if key == "throttled_time":
            return {"throttled_ms": int(value) / 1e6}
    return {}


def parse_pressure(text: str, key: str) -> Dict[str, float]:
    """``/proc/pressure/<resource>``: microseconds in which some task
    was stalled on it."""
    for line in text.splitlines():
        if line.startswith("some"):
            return {key: int(line.rsplit("total=", 1)[1]) / 1e3}
    return {}


def parse_steal(text: str) -> Dict[str, float]:
    """``/proc/stat``'s first line: the ``steal`` column, ticks."""
    f = text.split("\n", 1)[0].split()
    return {"steal_ms": int(f[8]) * 1e3 / _CLK_TCK} if len(f) > 8 else {}


def _cgroup_cpu_stat() -> List[str]:
    """Where this process's cgroup may keep ``cpu.stat``, likeliest
    first (v2, v2 under ``unified``, v1's ``cpu`` controller, the root)."""
    found = []
    try:
        with open("/proc/self/cgroup") as f:
            for line in f:
                _, ctl, path = line.rstrip("\n").split(":", 2)
                if not ctl:
                    found += [f"/sys/fs/cgroup{path}/cpu.stat",
                              f"/sys/fs/cgroup/unified{path}/cpu.stat"]
                elif "cpu" in ctl.split(","):
                    found.append(f"/sys/fs/cgroup/{ctl}{path}/cpu.stat")
    except (OSError, ValueError):
        pass
    return found + ["/sys/fs/cgroup/cpu.stat"]


class Accounts:
    """Descriptors kept open on what the kernel counts for this process,
    one ``pread`` each a reading. A reading is a dict of running totals
    (milliseconds, ``majflt`` a count) under the names a span's ``args``
    use; a file that is not there, or reads as something else, leaves its
    fields out. Opened by the thread that reads: ``thread-self`` is the
    opener's own."""

    def __init__(self, files: Optional[Dict[str, Any]] = None) -> None:
        files = files if files is not None else {
            "own": "/proc/thread-self/schedstat", "stat": "/proc/self/stat",
            "cgroup": _cgroup_cpu_stat(), "psi_cpu": "/proc/pressure/cpu",
            "psi_mem": "/proc/pressure/memory", "psi_io": "/proc/pressure/io",
            "machine": "/proc/stat", "tasks": "/proc/self/task"}
        self._tasks = files.get("tasks")
        self._fds: Dict[str, int] = {}
        self._threads: Dict[int, int] = {}
        for key, paths in files.items():
            if key == "tasks":
                continue
            for path in ([paths] if isinstance(paths, str) else paths):
                fd = self._open(path)
                if fd is not None:
                    self._fds[key] = fd
                    break

    @staticmethod
    def _open(path: str) -> Optional[int]:
        try:
            return os.open(path, os.O_RDONLY)
        except OSError:
            return None

    def _read(self, key: str, parse, *more) -> Dict[str, float]:
        fd = self._fds.get(key)
        if fd is None:
            return {}
        try:
            return parse(os.pread(fd, 4096, 0).decode(), *more)
        except (OSError, ValueError, IndexError):
            return {}

    def read(self) -> Dict[str, float]:
        out = self._read("own", parse_schedstat)
        out.update(self._read("stat", parse_stat))
        out.update(self._read("cgroup", parse_cpu_stat))
        out.update(self._read("psi_cpu", parse_pressure, "psi_cpu_ms"))
        out.update(self._read("psi_mem", parse_pressure, "psi_mem_ms"))
        out.update(self._read("psi_io", parse_pressure, "psi_io_ms"))
        out.update(self._read("machine", parse_steal))
        return out

    def threads(self, tids: Iterable[int]) -> Dict[int, Dict[str, float]]:
        """The scheduler account of each of the process's threads named
        (kernel ids); a thread that is gone is left out."""
        tids = [t for t in tids if t]
        for tid in set(self._threads) - set(tids):
            os.close(self._threads.pop(tid))
        out = {}
        for tid in tids:
            if tid not in self._threads and self._tasks:
                fd = self._open(f"{self._tasks}/{tid}/schedstat")
                if fd is not None:
                    self._threads[tid] = fd
            fd = self._threads.get(tid)
            if fd is not None:
                try:
                    out[tid] = parse_schedstat(os.pread(fd, 256, 0).decode())
                except (OSError, ValueError):
                    os.close(self._threads.pop(tid))
        return out

    def close(self) -> None:
        for fd in list(self._fds.values()) + list(self._threads.values()):
            os.close(fd)
        self._fds.clear()
        self._threads.clear()


class _GcClock:
    """The one ``gc.callbacks`` entry: each collection's length into two
    plain integers. It writes no span: a span from the collecting thread
    would land in that thread's ring."""

    def __init__(self) -> None:
        self.pause_ns = 0
        self.gen2 = 0
        self._t0 = 0

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        if phase == "start":
            self._t0 = time.monotonic_ns()
        elif self._t0:
            self.pause_ns += time.monotonic_ns() - self._t0
            self._t0 = 0
            if info.get("generation") == 2:
                self.gen2 += 1


# -------------------------------------------------------------- the verdict

def classify(a: Dict[str, Any]) -> str:
    """What a stall of ``a["ms"]`` was, from the accounts' changes across
    it. ``starved``: the watcher queued for a CPU for half of it or more,
    or its cgroup was throttled for as long. ``interpreter_held``: it
    slept (neither ran nor queued: a thread that waits for the GIL
    sleeps) for half of it or more while the process burned CPU for half
    of it or more. Where nobody ran (the process's CPU under a tenth of
    it): ``blocked_memory`` if major faults or memory pressure grew,
    ``blocked_io`` if block I/O delay or I/O pressure did, ``frozen`` if
    nothing did. Else ``unknown``. A kernel that keeps no ``schedstat``
    leaves ``runq_ms`` out: the watcher's queueing is then not seen, and
    a starved process is told by its cgroup's throttling alone."""
    ms = a["ms"]
    run, cpu = a.get("run_ms"), a.get("proc_cpu_ms")
    if run is None or cpu is None or ms <= 0:
        return "unknown"
    runq = a.get("runq_ms", 0.0)
    if runq >= ms / 2 or a.get("throttled_ms", 0) >= ms / 2:
        return "starved"
    if ms - run - runq >= ms / 2 and cpu >= ms / 2:
        return "interpreter_held"
    if cpu < ms / 10 and run + runq < ms / 10:
        if a.get("majflt", 0) > 0 or a.get("psi_mem_ms", 0) >= ms / 10:
            return "blocked_memory"
        if a.get("blkio_ms", 0) > 0 or a.get("psi_io_ms", 0) >= ms / 10:
            return "blocked_io"
        return "frozen"
    return "unknown"


def _changes(cur: Dict[str, float], prev: Dict[str, float]) -> Dict[str, Any]:
    return {k: round(v - prev[k], 3) for k, v in cur.items() if k in prev}


def _frames_of(frame: Any, depth: int) -> str:
    out = []
    while frame is not None and len(out) < depth:
        code = frame.f_code
        out.append(f"{os.path.basename(code.co_filename)}:{frame.f_lineno} "
                   f"{code.co_name}")
        frame = frame.f_back
    return " < ".join(out)


def stacks(depth: int, wanted: Any = None) -> Dict[str, str]:
    """Every other thread's name and innermost ``depth`` frames (those
    that ``wanted(ident, name)`` takes). The caller holds the
    interpreter, so no thread moves meanwhile."""
    names = {t.ident: t.name for t in threading.enumerate()}
    me = threading.get_ident()
    return {names.get(ident, hex(ident)): _frames_of(frame, depth)
            for ident, frame in sys._current_frames().items()
            if ident != me and (wanted is None
                                or wanted(ident, names.get(ident)))}


def stacks_text(frames: Dict[str, str], limit: int = STACKS_MAX) -> str:
    """A line a thread, threads that wait last, ``limit`` bytes at most."""
    lines = sorted(frames.items(), key=lambda kv: kv[1].startswith(_WAITS))
    return "\n".join(f"{name}: {fr}" for name, fr in lines)[:limit]


# -------------------------------------------------------------- the watcher

class ProcWatch:
    """One watcher thread; ``acquire`` / ``release`` keep the process's."""

    def __init__(self, metrics: Any) -> None:
        self.metrics = metrics
        self.engines: List[Any] = []
        # the newest stall and the deadline being slept to (monotonic ns);
        # written by the watcher alone, read by ``stalled_within``
        self.last_stall: Tuple[int, int] = (0, 0)
        self.due_ns = 0
        self._gc = _GcClock()
        self._stopping = False
        self._thread: Optional[threading.Thread] = None
        self._accounts: Optional[Accounts] = None
        self._prev: Dict[str, float] = {}
        self._prev_ns = 0
        self._threads_prev: Dict[int, Dict[str, float]] = {}
        self._history: Deque[Tuple[int, Dict[str, float]]] = (
            collections.deque(maxlen=HISTORY))
        self._late_engines: set = set()

    # ---------------------------------------------------------- lifecycle

    def start(self) -> "ProcWatch":
        if self._thread is None:
            gc.callbacks.append(self._gc)
            self._thread = threading.Thread(
                target=self._run, daemon=True, name="swarmdb-procwatch")
            self._thread.start()
        return self

    def stop(self) -> None:
        self._stopping = True
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        if self._gc in gc.callbacks:
            gc.callbacks.remove(self._gc)

    # ------------------------------------------------------------ readers

    def stalled_within(self, age_s: float, now: int = 0) -> float:
        """Seconds of the last ``age_s`` in which the process stood
        still: the newest stall's part of them, or the part of a stall
        the watcher has not woken from yet (a caller that woke first)."""
        now = now or time.monotonic_ns()
        since = now - int(age_s * 1e9)
        t0, t1 = self.last_stall
        got = min(t1, now) - max(t0, since)
        due = self.due_ns
        if due and now - due >= STALL_S * 1e9:
            got = max(got, now - max(due, since))
        return max(0, got) / 1e9

    # ----------------------------------------------------------- the loop

    def _run(self) -> None:
        self._accounts = Accounts()
        counters = self.metrics.counters
        c_ticks, c_late, c_awake = (counters["process_watch_ticks"],
                                    counters["process_wake_late_us"],
                                    counters["process_watch_awake_us"])
        tick, stall = int(TICK_S * 1e9), int(STALL_S * 1e9)
        cpu_prev = time.process_time_ns()
        self._prev = self._cumulative(cpu_prev)
        self._prev_ns = time.monotonic_ns()
        ticks = late_max = late_sum = awake = 0
        self.due_ns = due = self._prev_ns + tick
        try:
            while not self._stopping:
                time.sleep(max(0.0, (due - time.monotonic_ns()) / 1e9))
                now = time.monotonic_ns()
                late = max(0, now - due)
                # the process's CPU clock (CLOCK_PROCESS_CPUTIME_ID: what
                # utime + stime of /proc/self/stat count, in nanoseconds
                # and not in ticks of 10 ms)
                cpu = time.process_time_ns()
                ticks += 1
                late_sum += late
                late_max = max(late_max, late)
                if late >= stall or ticks >= SAMPLE_TICKS:
                    # awake_ms: wake to next sleep on the wall clock, what
                    # the watcher costs where the kernel's per-thread CPU
                    # clock is a sampled one (up to the tick before this)
                    since = self._prev_ns
                    args = self._sample(now, cpu, {
                        "ticks": ticks, "late_ms_max": round(late_max / 1e6, 3),
                        "late_ms_sum": round(late_sum / 1e6, 3),
                        "awake_ms": round(awake / 1e6, 3)})
                    c_ticks.inc(ticks)
                    c_late.inc(late_sum // 1000)
                    c_awake.inc(awake // 1000)
                    ticks = late_max = late_sum = awake = 0
                    if late >= stall:
                        self._stall(due, now, since, args,
                                    (cpu - cpu_prev) / 1e6)
                    self._engines_late(now)
                cpu_prev = cpu
                due += tick
                asleep = time.monotonic_ns()
                awake += asleep - now
                if due <= asleep:   # deadlines a stall ran over are not made up
                    due = asleep + tick
                self.due_ns = due
        except Exception:
            logger.exception("procwatch: the watcher died")
        finally:
            self.due_ns = 0
            self._accounts.close()

    def _cumulative(self, cpu_ns: int) -> Dict[str, float]:
        cur = self._accounts.read()
        if "run_ms" not in cur:
            # no schedstat: the thread's own CPU clock, and no runq_ms
            cur["run_ms"] = time.thread_time_ns() / 1e6
        cur["proc_cpu_ms"] = cpu_ns / 1e6
        cur["gc_ms"] = self._gc.pause_ns / 1e6
        cur["gc_gen2"] = self._gc.gen2
        if hasattr(time, "CLOCK_BOOTTIME"):
            # grows while the machine is suspended
            cur["boottime_gap_ms"] = (
                time.clock_gettime_ns(time.CLOCK_BOOTTIME)
                - time.monotonic_ns()) / 1e6
        return cur

    def _sample(self, now: int, cpu_ns: int,
                args: Dict[str, Any]) -> Dict[str, Any]:
        cur = self._cumulative(cpu_ns)
        args.update(_changes(cur, self._prev))
        # the engines' loop threads, each against its own last reading: a
        # restarted loop is another thread and starts from nothing
        read = self._accounts.threads(
            getattr(e, "_native_id", None) for e in self.engines)
        run = runq = 0.0
        for tid, acct in read.items():
            prev = self._threads_prev.get(tid, acct)
            run += acct["run_ms"] - prev["run_ms"]
            runq += acct["runq_ms"] - prev["runq_ms"]
        self._threads_prev = read
        counters = self.metrics.counters
        if read:
            args.update(engine_threads=len(read), engine_run_ms=round(run, 3),
                        engine_runq_ms=round(runq, 3))
            counters["engine_thread_run_us"].inc(int(run * 1e3))
            counters["engine_thread_runq_wait_us"].inc(int(runq * 1e3))
            counters["engine_thread_watch_us"].inc(
                len(read) * (now - self._prev_ns) // 1000)
        counters["process_gc_pause_us"].inc(int(args.get("gc_ms", 0) * 1e3))
        TRACER.span_end(self._prev_ns, "process.sample", cat="process",
                        args=args)
        self._history.append((self._prev_ns, self._prev))
        self._prev, self._prev_ns = cur, now
        return args

    def _stall(self, due: int, now: int, since: int,
               sample: Dict[str, Any], cpu_ms: float) -> None:
        """The sample just taken (from ``since``) holds the stall and,
        ``over_ms``, up to a sample's length before it, in which the
        watcher itself hardly ran or queued but the program's threads
        burned CPU: the process's CPU is taken from the tick before."""
        ms = (now - due) / 1e6
        args = dict(sample, ms=round(ms, 3), proc_cpu_ms=round(cpu_ms, 3),
                    over_ms=round((now - since) / 1e6, 3))
        args["beat_age_s"] = [round(e.beat_age_s(), 3) for e in self.engines]
        args["verdict"] = verdict = classify(args)
        args["stacks"] = stacks_text(stacks(3))
        self.last_stall = (due, now)
        TRACER.span_end(due, "process.stall", cat="process", args=args)
        self.metrics.counters["process_stalls"].inc()
        self.metrics.counters["process_stall_us"].inc((now - due) // 1000)
        cls = TRACER._annotation_cls()
        if cls is not None:
            # a mark on the profiler's clock, never a span across a sleep
            with cls("process.stall", ms=ms, verdict=verdict):
                pass
        if ms >= WARN_S * 1e3:
            logger.warning(
                "process stood still %.3fs: %s (%s); threads not waiting: %s",
                ms / 1e3, verdict,
                " ".join(f"{k}={v}" for k, v in args.items()
                         if isinstance(v, (int, float)) and k != "ms"),
                " | ".join(ln for ln in args["stacks"].splitlines()
                           if not ln.split(": ", 1)[-1].startswith(_WAITS)
                           )[:600])

    def _engines_late(self, now: int) -> None:
        """The other kind: the watcher is on time, so it holds the
        interpreter itself, and an engine's beat is old. Once a stall."""
        for lane, eng in enumerate(self.engines):
            thread = getattr(eng, "_thread", None)
            if thread is None or not thread.is_alive():
                continue
            age = eng.beat_age_s()
            if age - self.stalled_within(age, now) < ENGINE_LATE_S:
                self._late_engines.discard(id(eng))
                continue
            if id(eng) in self._late_engines:
                continue
            self._late_engines.add(id(eng))
            beat = now - int(age * 1e9)
            base_ns, base = next(
                (h for h in self._history if h[0] >= beat), self._history[0])
            args = dict(_changes(self._prev, base), lane=lane,
                        beat_age_ms=round(age * 1e3, 3),
                        in_step=bool(getattr(eng, "_in_step", False)),
                        over_ms=round((now - base_ns) / 1e6, 3),
                        frames=stacks(5, lambda ident, name: (
                            ident == thread.ident or name is None
                            or name.startswith(("tpu-", "Dummy-")))))
            TRACER.span_end(beat, "process.engine_late", cat="process",
                            args=args)


# ------------------------------------------------------ one a process

_LOCK = threading.Lock()
_WATCH: Optional[ProcWatch] = None
_USERS = 0


def acquire(metrics: Any, engines: Iterable[Any]) -> Optional[ProcWatch]:
    """Start the process's watcher, or join the one that runs (it keeps
    the registry of whoever started it), and have it watch ``engines``.
    ``None``, and nothing started, where the tracer is off."""
    global _WATCH, _USERS
    if not TRACER.enabled:
        return None
    with _LOCK:
        if _WATCH is None:
            _WATCH = ProcWatch(metrics).start()
        _USERS += 1
        _WATCH.engines = _WATCH.engines + [
            e for e in engines if e not in _WATCH.engines]
        return _WATCH


def release(engines: Iterable[Any]) -> None:
    """Give up one ``acquire``; the last one stops the watcher."""
    global _WATCH, _USERS
    with _LOCK:
        if _WATCH is None:
            return
        gone = list(engines)
        _WATCH.engines = [e for e in _WATCH.engines if e not in gone]
        _USERS -= 1
        if _USERS <= 0:
            _WATCH.stop()
            _WATCH, _USERS = None, 0


def process_late_s(age_s: float) -> float:
    """Of the last ``age_s`` seconds, those in which the whole process
    stood still by the watcher's record; 0 where none runs."""
    watch = _WATCH
    return watch.stalled_within(age_s) if watch is not None else 0.0
