#!/usr/bin/env python3
"""Run one cell of the benchmark once, in this process, and print the
result as the last line of standard output.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are data
(``BENCHMARK.json`` and the files under ``benchmark/``): see
``benchmark/README.md``. Without a TPU, or with fewer chips than the cell
asks for, the run fails and prints no result; ``--platform cpu`` exists for
the rehearsal and the tests only and marks its output so. ``--sweep
r1,r2,...`` offers several rates in one process to find the knee.
"""

from __future__ import annotations

import time

T_START = time.time()          # set-up counts from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.harness import spec as specs  # noqa: E402
from benchmark.harness import stats  # noqa: E402

DRAIN_S = 60.0          # after the last message was due
TRACE_S = 8.0           # the traced span, in the middle of the window
SAMPLE_EVERY = 0.05
CHECK_SAMPLE = 4


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def counters(db) -> dict:
    return dict(db.metrics.snapshot()["counters"])


def delta(after: dict, before: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in after.items()}


def publish_hist():
    from swarmdb_tpu.obs.metrics import HIST_PUBLISH

    return HIST_PUBLISH.snapshot()


def queue_waits(db) -> list:
    """The engine's ``queue_wait_s`` observations in arrival order (the
    reservoir keeps the newest 4096)."""
    hist = db.metrics.latencies["queue_wait_s"]
    with hist._lock:
        return list(hist._ring)


def device_facts(devs, chips: int) -> dict:
    peak = 0
    for d in devs[:chips]:
        peak = max(peak, int((d.memory_stats() or {}).get(
            "peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def turns_before_reply(rows) -> int:
    """Window messages that were due before their sender had read the
    reply to its previous message: the generator spaces a conversation's
    turns by a fixed allowance, and this says how often it was too short."""
    n, last = 0, {}
    for r in sorted(rows, key=lambda r: r["due"]):
        prev = last.get(r["sender"])
        if (prev is not None and r["phase"] == "window"
                and (prev["reply_t"] is None or prev["reply_t"] > r["due"])):
            n += 1
        last[r["sender"]] = r
    return n


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--spec", default=specs.DEFAULT_SPEC,
                    help="another BENCHMARK.json (the tests' tiny one)")
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rates: find the knee, no result")
    ap.add_argument("--out", default=None,
                    help="directory for the run's facts and trace summary")
    args = ap.parse_args(argv)

    cell = specs.load_cell(args.spec, args.workload)
    seconds = float(args.seconds if args.seconds is not None
                    else cell.run_seconds)
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{cell.chips}").strip()

    import jax

    devs = jax.devices()
    if devs[0].platform != args.platform:
        print(f"benchmark: jax found {devs[0].platform!r}, not "
              f"{args.platform!r}; no result", file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"benchmark: cell {cell.name} needs {cell.chips} chips, jax "
              f"found {len(devs)}; no result", file=sys.stderr)
        return 2

    from swarmdb_tpu.utils.xla_cache import enable_compile_cache

    from benchmark.harness import stack as stacks

    cache_dir = enable_compile_cache()
    out_dir = args.out
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    with tempfile.TemporaryDirectory(prefix="swarmbench_") as tmp:
        log(f"{cell.name}: {devs[0].device_kind} x{len(devs)}, cache "
            f"{cache_dir}; building {cell.config['name']}")
        stack = stacks.Stack(cell.config, args.seed, tmp)
        warm_s = stack.start()
        log(f"warm in {warm_s:.1f}s, {stack.compiled_count()} programs")
        try:
            if args.sweep:
                return sweep(stack, cell, args, seconds)
            return measure(stack, cell, args, seconds, devs, tmp, out_dir,
                           {"warmup_s": warm_s, "cache_dir": cache_dir})
        finally:
            if not stack.stopped:
                stack.stop()


def make_plan(cell, seed: int, seconds: float, rate=None) -> dict:
    traffic = dict(cell.traffic)
    if rate is not None:
        traffic["rate_per_s"] = rate
    gen = specs.load_generator(traffic["generator"])
    return gen.plan(traffic, seed, seconds)


def measure(stack, cell, args, seconds, devs, tmp, out_dir, facts) -> int:
    import jax

    from benchmark.harness import check, loadgen, trace_reduce

    plan = make_plan(cell, args.seed, seconds)
    driver = loadgen.Driver(stack, plan)
    driver.prepare()
    marks = {}

    def mark(name):
        marks[name] = {"t": time.time(), "counters": counters(stack.db),
                       "hist": publish_hist(),
                       "n_wait": len(queue_waits(stack.db)),
                       "compiled": stack.compiled_count()}

    trace_dir = os.path.join(tmp, "trace")
    at = {0.0: lambda: mark("start"), seconds: lambda: mark("end")}
    if args.trace:
        lo = max(0.1, (seconds - TRACE_S) / 2)
        hi = min(seconds - 0.1, lo + TRACE_S)

        def start_trace():
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            mark("trace_start")

        def stop_trace():
            mark("trace_end")
            # writing the trace takes seconds: off this thread, or the
            # window's end would be marked late
            t = threading.Thread(target=jax.profiler.stop_trace,
                                 name="bench-trace-stop")
            t.start()
            marks["trace_thread"] = t

        at[lo], at[hi] = start_trace, stop_trace
    log(f"{len(plan['arrivals'])} messages planned; window {seconds:.0f}s")
    # what set-up allocated stays: the collector then has only the run's
    # own garbage to look at, and pauses less inside the window
    gc.collect()
    gc.freeze()
    driver.run(seconds, DRAIN_S,
               sample_every=SAMPLE_EVERY if args.trace else 0.0, at=at)
    if "trace_thread" in marks:
        marks["trace_thread"].join()
    t0 = driver.t0
    setup_s = t0 - T_START
    rows = driver.joined()
    win = [r for r in rows if r["phase"] == "window"]
    compiles = marks["end"]["compiled"] - marks["start"]["compiled"]
    device = device_facts(devs, cell.chips)
    stack.stop()
    log(f"window done: {len(win)} messages, "
        f"{sum(r['reply_t'] is not None for r in win)} replied; checking")

    # ---- correct -----------------------------------------------------
    faults = check.replies_ok(win)
    failed = sum(1 for r in win if r["replies"] < 1)
    recs = [stack.recorder.get(r["id"]) for r in win if r["id"]]
    sample = check.sample([r for r in recs if r], args.seed, CHECK_SAMPLE)
    t_chk = time.time()
    reference = cell.reference()
    gaps = check.logit_gaps(stack, sample, reference) if sample else []
    gap_ok = bool(gaps) and max(gaps) <= check.LOGIT_TOL
    correct = bool(gap_ok and not faults and compiles == 0
                   and len(sample) >= min(CHECK_SAMPLE, len(win)))
    facts.update({
        "cell": cell.name, "seed": args.seed, "seconds": seconds,
        "broker": type(stack.db.broker).__name__,
        "n_layers": stack.cfg.n_layers, "max_batch": stack.max_batch,
        "kv_pool_tokens": stack.serving.get("kv_pool_tokens"),
        "messages_sent": len(rows), "window_messages": len(win),
        "window_replied": sum(r["reply_t"] is not None for r in win),
        "turns_before_reply": turns_before_reply(rows),
        "compiles_in_window": compiles, "reply_faults": faults[:10],
        "logit_gaps": gaps, "logit_tol": check.LOGIT_TOL,
        "reference": cell.config["reference"],
        "routing_followed": check.follows_routing(reference),
        "checked_lengths": [len(r["prompt"]) + len(r["tokens"])
                            for r in sample],
        "check_s": time.time() - t_chk, "setup_s": setup_s,
        "counters_window": delta(marks["end"]["counters"],
                                 marks["start"]["counters"]),
        "peak_bytes": device["memory_peak_bytes"]})

    # ---- metrics -----------------------------------------------------
    e2e = stats.end_to_end(rows, t0, seconds)
    e2e["setup_s"] = setup_s
    metrics = {}
    if not args.trace:
        for m in cell.end_to_end:
            v = e2e.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": correct, "attempted": len(win), "failed": failed}
    if args.trace:
        trace = red = None
        if device["platform"] == "tpu":
            xplane = trace_reduce.find_xplane(trace_dir)
            trace = trace_reduce.load_xplane(xplane)
            if out_dir:
                with open(os.path.join(out_dir, "trace_peek.json"),
                          "w") as f:
                    json.dump(trace_reduce.peek(xplane), f)
            red = trace_reduce.reduce(trace)
            device["busy_s"], device["window_s"] = (red["busy_s"],
                                                    red["window_s"])
            result["breakdown"] = red["breakdown"]
            if out_dir:
                with open(os.path.join(out_dir, "trace_summary.json"),
                          "w") as f:
                    json.dump(trace_reduce.summary(trace), f)
        waits = queue_waits(stack.db)
        ctx = {
            "window_rows": win, "t0": t0, "seconds": seconds,
            "counters": facts["counters_window"],
            "publish_hist": (marks["start"]["hist"], marks["end"]["hist"]),
            "queue_wait_s": waits[marks["start"]["n_wait"]:
                                  marks["end"]["n_wait"]],
            "window_samples": [s for s in driver.samples
                               if t0 <= s["t"] < t0 + seconds],
            "trace": red,
            "trace_counters": delta(marks["trace_end"]["counters"],
                                    marks["trace_start"]["counters"]),
            "decode_chunk": stack.serving["decode_chunk"],
            "max_batch": stack.max_batch, "device_kind": device["kind"],
            "model": stack.cfg, "config": stack.cfg_file, "rows": rows,
            "page_size": stack.serving["page_size"],
            "engine_records": dict(stack.recorder.records),
            "trace_span": (marks["trace_start"]["t"],
                           marks["trace_end"]["t"]),
            "notes": {},
        }
        for m in cell.per_layer:
            v = specs.load_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        facts["end_to_end_traced"] = e2e
        facts["notes"] = ctx["notes"]
        facts["trace_reduction"] = (
            {k: red[k] for k in ("window_s", "busy_s", "programs")}
            | {"kernels": {k: v for k, v in red["kernels"].items()
                           if "attention" in k}}
            if red else None)
    result["metrics"] = metrics
    result["device"] = device
    if args.platform == "cpu":
        result["rehearsal"] = "cpu: no number here is a device metric"
    # each number compared beside its limit, last in the line
    result["compared"] = {
        "logit_gap_max": [max(gaps, default=None), check.LOGIT_TOL],
        "reply_faults": [len(faults), 0], "compiles_in_window": [compiles, 0],
        "records_checked": [len(sample), min(CHECK_SAMPLE, len(win))]}
    log(f"compared: logit gaps {[round(g, 4) for g in gaps]} each <= "
        f"{check.LOGIT_TOL}"
        f"{', routing followed' if facts['routing_followed'] else ''}; "
        f"{len(faults)} reply faults, {compiles} "
        f"compiles in the window, both == 0; {len(sample)} records >= "
        f"{min(CHECK_SAMPLE, len(win))}; correct {correct}")
    print(json.dumps(facts), flush=True)
    if out_dir:
        with open(os.path.join(out_dir, "facts.json"), "w") as f:
            json.dump(facts, f)
    print(json.dumps(result), flush=True)
    return 0


def sweep(stack, cell, args, seconds) -> int:
    """Several rates, one set-up: for each, offered and completed messages
    a second and the engine's queue at the middle and the end. The knee is
    the highest rate at which completed keeps up with offered and the
    queue does not grow."""
    from benchmark.harness import loadgen

    for i, rate in enumerate(float(r) for r in args.sweep.split(",")):
        plan = make_plan(cell, args.seed + i, seconds, rate)
        for a in plan["arrivals"]:      # no history from the rate before
            a["sender"] = f"r{i}-{a['sender']}"
        driver = loadgen.Driver(stack, plan)
        driver.prepare()
        q = {}
        at = {seconds / 2: lambda: q.__setitem__("mid", stack.queued()),
              seconds: lambda: q.__setitem__("end", stack.queued())}
        driver.run(seconds, DRAIN_S, at=at)
        rows = driver.joined()
        t0 = driver.t0
        win = [r for r in rows if r["phase"] == "window"]
        done = [r for r in win if r["reply_t"] is not None
                and r["reply_t"] < t0 + seconds]
        e2e = stats.end_to_end(rows, t0, seconds)
        print(json.dumps({
            "sweep": cell.name, "rate_per_s": rate,
            "offered_per_s": len(win) / seconds,
            "completed_per_s": len(done) / seconds,
            "queued_mid": q.get("mid"), "queued_end": q.get("end"),
            "unanswered": sum(r["reply_t"] is None for r in win),
            "turns_before_reply": turns_before_reply(rows), **e2e}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
