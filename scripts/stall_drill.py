#!/usr/bin/env python3
"""Stall drill: does the process's watcher name a stall that is provoked?

    python3 scripts/stall_drill.py --platform cpu          # the tiny stack
    chiprun -- python3 scripts/stall_drill.py --spec BENCHMARK.json \\
        --workload mistral7b.chat

Brings a cell's stack up with ``benchmark/harness/stack.Stack`` (imported,
not edited), sends its traffic at a light rate, and provokes inside the
window, ``--hold`` seconds each (2 by default) and 5 s apart, the three
kinds of stall that ``swarmdb_tpu/obs/procwatch.py`` tells apart:

- ``stopped``: a child sends this process ``SIGSTOP`` and then ``SIGCONT``
  (nobody runs, nobody queues: ``frozen``);
- ``held``: a side thread keeps the interpreter in one C call, a
  backtracking ``re.match`` (the watcher sleeps on the GIL while the
  process burns a CPU: ``interpreter_held``, with the holder's frame in
  ``stacks``);
- ``crowded``: one busy child a CPU and two more (the watcher queues for a
  CPU: ``starved``, where the machine's scheduler lets a sleeper wait that
  long; a scheduler that favours the thread that slept reads a few
  milliseconds of ``runq_ms`` and no stall at all, and that is then the
  reading).

One JSON line a provocation: what was provoked and for how long (measured
where it was done), every ``process.stall`` span that overlaps it, and the
``process.sample`` accounts summed over it. Then a summary line. Exit 0
when ``stopped`` and ``held`` each gave one stall whose length is within
0.1 s of what was provoked; ``crowded`` is reported and not judged.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import subprocess
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

DRAIN_S = 60.0
FIRST_AT_S, APART_S = 3.0, 5.0
PATTERN = r"(a+)+$"

STOPPER = """
import os, signal, sys, time
hold = float(sys.argv[1])
time.sleep(0.2)
t0 = time.time()
os.kill(os.getppid(), signal.SIGSTOP)
time.sleep(hold)
os.kill(os.getppid(), signal.SIGCONT)
print(t0, time.time(), flush=True)
"""
BUSY = """
import sys, time
t0 = time.time()
end = t0 + float(sys.argv[1])
while time.time() < end:
    pass
print(t0, time.time(), flush=True)
"""


def backtracking(seconds: float) -> str:
    """A text on which ``PATTERN`` backtracks for about ``seconds`` (the
    time doubles a character); found before the window, on this machine."""
    n = 16
    while True:
        t = time.perf_counter()
        re.match(PATTERN, "a" * n + "b")
        took = time.perf_counter() - t
        if took >= 0.2:
            extra = max(0, round(math.log2(seconds / took)))
            return "a" * (n + extra) + "b"
        n += 1


class Provocations:
    """The three, each started from the load driver's thread at its
    offset and measured where it runs; ``done()`` waits for them."""

    def __init__(self, hold_s: float) -> None:
        self.hold_s = hold_s
        self.text = backtracking(hold_s)
        self.children = {}
        self.spans = {}

    def _child(self, kind: str, code: str) -> None:
        self.children.setdefault(kind, []).append(subprocess.Popen(
            [sys.executable, "-c", code, str(self.hold_s)],
            stdout=subprocess.PIPE, text=True))

    def stopped(self) -> None:
        self._child("stopped", STOPPER)

    def held(self) -> None:
        def hold():
            t0 = time.time()
            re.match(PATTERN, self.text)
            self.spans["held"] = (t0, time.time())
            # the watcher names the dump's threads once it runs again
            time.sleep(0.5)

        self._holder = threading.Thread(target=hold, name="drill-holder")
        self._holder.start()

    def crowded(self) -> None:
        for _ in range((os.cpu_count() or 1) + 2):
            self._child("crowded", BUSY)

    def done(self) -> dict:
        self._holder.join(timeout=60)
        for kind, procs in self.children.items():
            times = [tuple(map(float, p.communicate(timeout=60)[0].split()))
                     for p in procs]
            self.spans[kind] = (min(t[0] for t in times),
                                max(t[1] for t in times))
        return self.spans


def report(kind: str, t0: float, t1: float, spans: list) -> dict:
    """What the watcher wrote across one provocation."""
    def end(e):
        return e["start_s"] + e["dur_us"] * 1e-6

    over = [e for e in spans if e["start_s"] < t1 + 0.05 and end(e) > t0]
    stalls = [e for e in over if e["name"] == "process.stall"]
    sums = {}
    for e in over:
        if e["name"] == "process.sample":
            for k, v in (e["args"] or {}).items():
                if (isinstance(v, (int, float))
                        and k not in ("late_ms_max", "engine_threads")):
                    sums[k] = round(sums.get(k, 0) + v, 3)
    return {"provoked": kind, "provoked_s": round(t1 - t0, 3),
            "stalls": [{"at_s": round(e["start_s"] - t0, 3),
                        "length_s": round(e["dur_us"] * 1e-6, 3),
                        **e["args"]} for e in stalls],
            "late_ms_max": max((e["args"]["late_ms_max"] for e in over
                                if e["name"] == "process.sample"),
                               default=None),
            "samples_sum": sums}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--spec", default=os.path.join(
        ROOT, "tests", "benchmark", "tiny", "spec.json"))
    ap.add_argument("--workload", default="tiny.chat")
    ap.add_argument("--seed", type=int, default=39)
    ap.add_argument("--rate", type=float, default=1.0,
                    help="messages a second: light, the drill's own")
    ap.add_argument("--hold", type=float, default=2.0,
                    help="seconds each provocation lasts")
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    args = ap.parse_args(argv)
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    provoke = Provocations(args.hold)       # before JAX: no chip is held
    import jax

    if jax.devices()[0].platform != args.platform:
        print(f"drill: jax found {jax.devices()[0].platform!r}, not "
              f"{args.platform!r}", file=sys.stderr)
        return 2
    from swarmdb_tpu.obs import TRACER
    from swarmdb_tpu.utils.xla_cache import enable_compile_cache

    from benchmark.harness import loadgen
    from benchmark.harness import spec as specs
    from benchmark.harness import stack as stacks

    enable_compile_cache()
    cell = specs.load_cell(args.spec, args.workload)
    seconds = FIRST_AT_S + 3 * APART_S
    with tempfile.TemporaryDirectory(prefix="stalldrill_") as tmp:
        stack = stacks.Stack(cell.config, args.seed, tmp)
        warm_s = stack.start()
        try:
            traffic = dict(cell.traffic, rate_per_s=args.rate)
            plan = specs.load_generator(traffic["generator"]).plan(
                traffic, args.seed, seconds)
            driver = loadgen.Driver(stack, plan)
            driver.prepare()
            at = {FIRST_AT_S + i * APART_S: f for i, f in enumerate(
                (provoke.stopped, provoke.held, provoke.crowded))}
            driver.run(seconds, DRAIN_S, at=at)
            provoked = provoke.done()
            rows = driver.joined()
        finally:
            stack.stop()
    spans = [e for e in TRACER.snapshot() if e["cat"] == "process"]
    lines = [report(kind, *provoked[kind], spans)
             for kind in ("stopped", "held", "crowded")]
    for line in lines:
        print(json.dumps(line), flush=True)
    late = [e for e in spans if e["name"] == "process.engine_late"]
    judged = {ln["provoked"]: (
        len(ln["stalls"]) == 1
        and abs(ln["stalls"][0]["length_s"] - ln["provoked_s"]) <= 0.1)
        for ln in lines[:2]}
    win = [r for r in rows if r["phase"] == "window"]
    summary = {
        "summary": True, "cell": cell.name, "warm_s": round(warm_s, 1),
        "platform": jax.devices()[0].platform,
        "device_kind": jax.devices()[0].device_kind,
        "cpus": os.cpu_count(), "window_messages": len(win),
        "window_replied": sum(r["reply_t"] is not None for r in win),
        "verdicts": {ln["provoked"]: [s["verdict"] for s in ln["stalls"]]
                     for ln in lines},
        "one_stall_within_0.1s": judged,
        "holders_frame_in_stacks": any(
            "drill-holder" in s.get("stacks", "")
            for s in lines[1]["stalls"]),
        "engine_late_spans": [dict(e["args"], at_s=e["start_s"])
                              for e in late],
        "stalls_in_all": sum(e["name"] == "process.stall" for e in spans)}
    print(json.dumps(summary), flush=True)
    return 0 if all(judged.values()) else 1


if __name__ == "__main__":
    sys.exit(main())
