"""Process: the longest stretch in which the whole process stood still:
the longest ``process.stall`` span (``swarmdb_tpu/obs/procwatch.py``: a
wake of the watcher thread 0.1 s late or more, from when it was due to
when it ran) that overlaps the window, at its whole length; 0 where the
watcher ran and wrote none. ``notes`` holds what the watcher read across
it: the ``verdict``, the kernel's accounts, when it was from the window's
start, and the head of the threads' stacks at the wake that ended it.
Beside it ``notes["process_engine_late"]`` holds the window's
``process.engine_late`` spans (an engine's beat a second old with the
watcher on time: where the loop thread and the callback threads stood),
which have no metric of their own. A program without the watcher gives
``None``."""
from benchmark.harness import spans

NAME = "process_stall_ms_max"
STACKS_HEAD = 600


def read(ctx):
    held = spans.engine_spans(ctx, NAME, cat="process")
    if not held:
        return None
    t0, t1 = ctx["t0"], ctx["t0"] + ctx["seconds"]
    late = [dict(e["args"], at_s=e["start_s"] - t0) for e in held
            if e["name"] == "process.engine_late"
            and e["end_s"] > t0 and e["start_s"] < t1]
    if late:
        ctx["notes"]["process_engine_late"] = late
    stalls = [e for e in held if e["name"] == "process.stall"
              and e["end_s"] > t0 and e["start_s"] < t1]
    if not stalls:
        return 0.0
    worst = max(stalls, key=lambda e: e["dur_us"])
    args = worst["args"]
    ctx["notes"][NAME] = {
        "verdict": args.get("verdict"), "at_s": worst["start_s"] - t0,
        "stalls": len(stalls),
        "accounts": {k: v for k, v in args.items()
                     if isinstance(v, (int, float))},
        "beat_age_s": args.get("beat_age_s"),
        "stacks": (args.get("stacks") or "")[:STACKS_HEAD]}
    return worst["dur_us"] * 1e-3
