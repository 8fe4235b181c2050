"""The reader of the session boundary's idle time (PR 45) on a canned
trace reduction: the idle seconds the reduction gave to ``engine.session``
and ``engine.admission`` over the sessions of the traced span; 0.0 where
sessions ran and neither phase is among the ten names (a traced line that
lacks an entry's metric is refused); nothing without a trace or a
session; and the parent commit, whose counters lack
``admission_planned_ahead``, reads like any other program."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import spec as specs  # noqa: E402

GAPS = [["engine.session", 0.354], ["engine.admission", 0.251],
        ["short gaps between operations", 0.012], ["engine.wait", 0.004]]
COUNTERS = {"engine_resident_sessions": 43, "engine_admitted": 88,
            "admission_planned_ahead": 61}


def read(gaps=GAPS, counters=COUNTERS, trace=True):
    ctx = {"t0": 1000.0, "seconds": 50.0, "spans": [], "ring_stats": [],
           "notes": {}, "counters": {}, "trace_counters": dict(counters),
           "trace": {"breakdown": {"idle_gaps": gaps}} if trace else None}
    value = specs.load_reader("session_boundary_idle_ms").read(ctx)
    return value, ctx["notes"].get("session_boundary_idle_ms")


def test_boundary_idle_is_both_phases_idle_seconds_over_the_sessions():
    value, note = read()
    assert value == pytest.approx(1e3 * (0.354 + 0.251) / 43)
    assert note == {"idle_s": {"engine.session": 0.354,
                               "engine.admission": 0.251},
                    "sessions": 43, "planned_ahead": 61, "admitted": 88}


@pytest.mark.parametrize("absent, left", [
    ("engine.session", 0.251), ("engine.admission", 0.354)])
def test_a_phase_that_is_not_among_the_names_counts_as_zero(absent, left):
    value, note = read([g for g in GAPS if g[0] != absent])
    assert value == pytest.approx(1e3 * left / 43)
    assert note["idle_s"][absent] == 0.0


def test_neither_phase_listed_reads_zero_and_not_nothing():
    value, note = read([["engine.wait", 0.5]])
    assert value == 0.0 and value is not None
    assert note["idle_s"] == {"engine.session": 0.0, "engine.admission": 0.0}


@pytest.mark.parametrize("counters, trace", [
    (COUNTERS, False),                              # an untraced run
    ({}, True),                                     # the scan path
    ({"engine_resident_sessions": 0}, True)])       # no session in the span
def test_nothing_without_a_trace_or_a_session(counters, trace):
    assert read(counters=counters, trace=trace) == (None, None)


def test_the_parent_reads_too_and_its_note_says_it_plans_nothing_ahead():
    value, note = read(counters={"engine_resident_sessions": 43,
                                 "engine_admitted": 88})
    assert value == pytest.approx(1e3 * 0.605 / 43)
    assert note["planned_ahead"] is None and note["admitted"] == 88


def test_boundary_idle_has_its_entry_for_all_three_cells():
    with open(ROOT / "BENCHMARK.json") as f:
        spec = json.load(f)
    assert spec["per_layer"][-1] == {
        "name": "session_boundary_idle_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "scheduler",
        "moves": "tpot_p90_ms",
        "workloads": ["mistral7b.chat", "lfm2-8b-a1b.chat",
                      "deepseek-v2.chat"]}
    assert spec["per_layer"][-1]["workloads"] == [
        w["name"] for w in spec["workloads"]]
