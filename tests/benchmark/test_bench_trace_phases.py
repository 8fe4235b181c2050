"""The unchanged ``trace_reduce.reduce`` and ``decode_ms_per_step`` on a
trace of a program that names its phases and its decode programs
(``fixtures/trace_phases.json``: ``trace_small.json`` with the decode
program called ``decode_resident_greedy`` and the host line holding the
engine's annotations round JAX's own events)."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import spec as specs  # noqa: E402
from benchmark.harness import trace_reduce as tr  # noqa: E402

FIXTURES = Path(__file__).parent / "fixtures"


def reduced(name):
    return tr.reduce(json.loads((FIXTURES / name).read_text()))


def test_an_enclosing_phase_names_the_gap():
    gaps = dict(reduced("trace_phases.json")["breakdown"]["idle_gaps"])
    # [9000, 10000): engine.admission.pack and the np.asarray inside it
    # both cover all of it; the phase starts first, is met first, and wins
    assert gaps["engine.admission.pack"] == pytest.approx(1000e-9)
    # [14000, 17000): engine.emit covers 2900 of it, np.asarray 2700
    assert gaps["engine.emit"] == pytest.approx(3000e-9)
    assert "np.asarray(jax.Array)" not in gaps
    assert gaps["PjitFunction(_prefill_ragged_insert)"] == pytest.approx(
        1000e-9)


def test_idle_named_by_program_share_reads_the_gaps():
    read = specs.load_reader("idle_named_by_program_share").read
    # 4000 of the 5000 ns a host event names go to engine.* phases; the
    # 1000 ns under nothing are not in the share
    assert read({"trace": reduced("trace_phases.json")}) == pytest.approx(
        80.0)
    assert read({"trace": reduced("trace_small.json")}) == 0.0
    assert read({"trace": None}) is None


@pytest.mark.parametrize("fixture,program", [
    ("trace_small.json", "_unknown"),
    ("trace_phases.json", "decode_resident_greedy")])
def test_decode_ms_per_step_whatever_the_decode_program_is_called(
        fixture, program):
    red = reduced(fixture)
    assert program in red["programs"]
    assert set(red["programs"]) == {program, "_prefill_ragged_insert"}
    ctx = {"trace": red, "trace_counters": {"engine_resident_chunks": 2},
           "decode_chunk": 8}
    # 7000 ns of leaf operations in the decode program over 16 steps
    assert specs.load_reader("decode_ms_per_step").read(ctx) == (
        pytest.approx(1e3 * 7000e-9 / 16))
    # and the prefill reader still finds its programs, not the decode one
    ctx["trace_counters"]["prefill_packed_tokens"] = 512
    assert specs.load_reader("prefill_ms_per_ktok").read(ctx) == (
        pytest.approx(1e3 * 6000e-9 / 0.512))
