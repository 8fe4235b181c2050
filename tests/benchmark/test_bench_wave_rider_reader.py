"""The reader of the wave riders' share (PR 43) on canned counters: what
it reads, 0 where tokens were generated and nobody rode, and ``None``
where the program has no such counter (the parent commit) or generated
nothing."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import spec as specs  # noqa: E402


def read(counters):
    return specs.load_reader("wave_rider_token_share").read(
        {"t0": 1000.0, "seconds": 50.0, "spans": [], "trace": None,
         "ring_stats": [], "notes": {}, "counters": counters,
         "trace_counters": {}})


def test_rider_share_is_rider_tokens_over_generated_tokens():
    assert read({"wave_rider_tokens": 1200,
                 "tokens_generated": 24000}) == pytest.approx(5.0)


def test_rider_share_is_zero_where_tokens_came_and_nobody_rode():
    got = read({"wave_rider_tokens": 0, "tokens_generated": 24000})
    assert got == 0.0 and got is not None


@pytest.mark.parametrize("counters", [
    {},                                       # nothing ran
    {"tokens_generated": 24000},              # the parent: no such counter
    {"wave_rider_tokens": 0},                 # no token in the window
    {"wave_rider_tokens": 0, "tokens_generated": 0}])
def test_rider_share_is_none_without_the_counter_or_a_token(counters):
    assert read(counters) is None


def test_rider_share_has_its_entry_for_both_cells():
    import json

    with open(ROOT / "BENCHMARK.json") as f:
        entry = [m for m in json.load(f)["per_layer"]
                 if m["name"] == "wave_rider_token_share"]
    assert entry == [{
        "name": "wave_rider_token_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "scheduler",
        "moves": "tpot_p90_ms",
        "workloads": ["mistral7b.chat", "lfm2-8b-a1b.chat"]}]
