"""Scheduler: how long running requests stand still while the engine
leaves its decode loop for an admission round, 90th percentile. Reads the
program's ``engine.session`` spans: the end of one session to the start of
the next of the same engine thread, where the two are consecutive loop
steps (``args["step"]``) and the later one took running slots over
(``args["carried"]``; ``slots`` where the program does not say). Pauses
that start in the window count. Host clock round host work: the prefill
the device still has queued when the next session is dispatched is not in
it."""
from benchmark.harness import spans
from benchmark.harness.stats import percentile

NAME = "decode_pause_ms_p90"


def read(ctx):
    held = spans.engine_spans(ctx, NAME)
    if held is None:
        return None
    by_thread = {}
    for e in held:
        if e["name"] == "engine.session":
            by_thread.setdefault(e["tid"], []).append(e)
    pauses = []
    for sessions in by_thread.values():
        sessions.sort(key=lambda e: e["start_s"])
        for prev, nxt in zip(sessions, sessions[1:]):
            over = nxt["args"].get("carried", nxt["args"].get("slots", 0))
            if (nxt["args"].get("step") == prev["args"].get("step", -2) + 1
                    and over > 0 and spans.in_window(ctx, prev["end_s"])):
                pauses.append((nxt["start_s"] - prev["end_s"]) * 1e3)
    return percentile(pauses, 90)
