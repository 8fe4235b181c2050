"""Seeded phase-pair violations (SWL501 on phase_begin/phase_end) — lint
fixture.

Not imported by anything; analyzed as text by tests/test_swarmlint.py.
"""

from swarmdb_tpu.obs import TRACER


def phase_begun_never_ended(x):
    t0 = TRACER.phase_begin("engine.thing")  # EXPECT: SWL501
    return x + 1 if t0 else x


def phase_discarded_stamp(x):
    TRACER.phase_begin("engine.thing")  # EXPECT: SWL501
    TRACER.phase_end(0, "engine.thing")
    return x


def span_end_does_not_balance_a_phase(tracer, work):
    # the pairs are judged apart: the annotation this phase opened is
    # closed by phase_end only
    t0 = tracer.phase_begin("engine.thing")  # EXPECT: SWL501
    out = work()
    tracer.span_end(t0, "engine.thing")
    return out


# swarmlint: hot
def hot_phase_balanced_ok(tracer, work):
    t0 = tracer.phase_begin("engine.thing")
    try:
        return work()
    finally:
        tracer.phase_end(t0, "engine.thing", cat="engine")
