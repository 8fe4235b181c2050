"""Scheduler: how long the device stands idle at a boundary between two
resident decode sessions, a session: the traced span's idle seconds that
the trace reduction gives to the phases ``engine.session`` (a session's
last block being processed, its drain, the next one's inputs) and
``engine.admission`` (the round between them, up to its wave's dispatch)
in ``trace["breakdown"]["idle_gaps"]``, over the sessions run in the span
(counter ``engine_resident_sessions``). In that time no running row
advances, so it is part of every running reply's time a token.

``idle_gaps`` holds the ten names with the most idle time, so a name that
is not in it had less than the tenth: where there were sessions and
neither name is listed this reads 0.0, not nothing. ``notes`` holds the
two sums, the sessions and, of the requests admitted in the span, those
whose plan was made before their round began (counters
``admission_planned_ahead`` / ``engine_admitted``; the first is absent
from a program that plans nothing ahead). Nothing where there is no trace
or no session ran in it (the scan path, the dense slab engine)."""

PHASES = ("engine.session", "engine.admission")


def read(ctx):
    tr = ctx["trace"]
    tc = ctx["trace_counters"]
    sessions = tc.get("engine_resident_sessions", 0)
    if tr is None or not sessions:
        return None
    gaps = dict(tr["breakdown"]["idle_gaps"])
    idle = {name: gaps.get(name, 0.0) for name in PHASES}
    ctx["notes"]["session_boundary_idle_ms"] = {
        "idle_s": idle, "sessions": sessions,
        "planned_ahead": tc.get("admission_planned_ahead"),
        "admitted": tc.get("engine_admitted")}
    return 1e3 * sum(idle.values()) / sessions
