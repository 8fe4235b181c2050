"""Scheduler: the longest stretch of the window in which no span of the
engine ended although a decode session or an admission round was open: in
a healthy run about one decode chunk, in a stalled one the stall. Reads
every ``cat="engine"`` span of the program's tracer (engine thread and
the runtime's callback threads together: one engine's work), takes the
gaps between consecutive span ends, keeps those whose second half lies
inside an ``engine.session`` or ``engine.admission`` span, and reports the
longest. ``notes`` names the deepest span open across it (the one that
began last), its ``step``, its thread and when it was, from the window's
start."""
from benchmark.harness import spans

NAME = "engine_stall_ms_max"
OPEN = ("engine.session", "engine.admission")


def read(ctx):
    held = spans.engine_spans(ctx, NAME)
    if not held:
        return None
    t0, t1 = ctx["t0"], ctx["t0"] + ctx["seconds"]
    ends = sorted({e["end_s"] for e in held if t0 <= e["end_s"] < t1})
    gaps = sorted(((b - a, a, b) for a, b in zip(ends, ends[1:])),
                  reverse=True)
    for length, a, b in gaps:
        # a phase carries the loop step that caused it and no request
        # id; a request's own spans (engine.admit is its whole wait in
        # the queue) name no phase. A phase is open across the gap if it
        # holds the gap's second half: the one that stalled began after
        # the end before it
        across = [e for e in held
                  if e["start_s"] <= (a + b) / 2 and e["end_s"] >= b
                  and "step" in e["args"] and e["rid"] is None]
        if not any(e["name"] in OPEN for e in across):
            continue
        deepest = max(across, key=lambda e: e["start_s"])
        ctx["notes"][NAME] = {
            "span": deepest["name"], "step": deepest["args"].get("step"),
            "thread": deepest["thread"], "at_s": a - t0,
            "span_ms": deepest["dur_us"] * 1e-3}
        return length * 1e3
    return None
