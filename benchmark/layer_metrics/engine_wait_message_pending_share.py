"""Service: the share of the window in which the engine waited for work
while a message was already published: 100 x (seconds of the window in
which an ``engine.wait`` phase was open on some lane AND at least one of
the window's messages was between the start of its ``serve.pickup`` span
and the end of its ``serve.request`` span) / the window's seconds. ``engine.wait`` is
the name under which the device's idle time of a cell below its knee
stands in ``breakdown.idle_gaps``; the rings' clock is the one the phases
are written on. 0.0 where the two never meet. ``notes`` holds ``wait_s``
(the union of the waits), ``pending_s`` (the union of the messages'
stretches) and ``overlap_s``, all inside the window. A program without
``serve.pickup`` (an older commit) reads nothing."""
from benchmark.harness import spans
from benchmark.harness.trace_reduce import overlap, union

NAME = "engine_wait_message_pending_share"


def read(ctx):
    serving = spans.engine_spans(ctx, NAME, cat="serving")
    engine = spans.engine_spans(ctx, NAME)
    if serving is None or engine is None:
        return None
    mids = {r["id"] for r in ctx["window_rows"] if r["id"]}
    begun = {e["rid"]: e["start_s"] for e in serving
             if e["name"] == "serve.pickup" and e["rid"] in mids}
    if not begun:
        return None
    lo, hi = ctx["t0"], ctx["t0"] + ctx["seconds"]
    waits = union([(e["start_s"], e["end_s"]) for e in engine
                   if e["name"] == "engine.wait"])
    # the messages' stretches, cut to the window
    pending = [(max(a, lo), min(b, hi)) for a, b in union(
        [(begun[e["rid"]], e["end_s"]) for e in serving
         if e["name"] == "serve.request" and e["rid"] in begun])
        if a < hi and b > lo]
    both = sum(overlap(waits, a, b) for a, b in pending)
    ctx["notes"][NAME] = {"wait_s": overlap(waits, lo, hi),
                          "pending_s": sum(b - a for a, b in pending),
                          "overlap_s": both}
    return 100.0 * both / ctx["seconds"]
