"""The generator: deterministic in the seed, the stated distributions, and
the same work for every seed in another order."""

import statistics
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import draws, spec  # noqa: E402

TINY = str(ROOT / "tests" / "benchmark" / "tiny")
# (mix, directory): the cell's own chat, and the tests' single-turn mix
MIXES = [("chat", None), ("digest", TINY)]


@pytest.mark.parametrize("mix,directory", MIXES)
def test_plan_is_deterministic_in_seed(mix, directory):
    traffic = spec.load_traffic(mix, directory)
    gen = spec.load_generator(traffic["generator"])
    a = gen.plan(traffic, 3_000_000_019, 20)
    assert gen.plan(traffic, 3_000_000_019, 20) == a
    assert gen.plan(traffic, 5, 20) != a


@pytest.mark.parametrize("mix,directory", MIXES)
def test_every_seed_sends_the_same_work(mix, directory):
    traffic = spec.load_traffic(mix, directory)
    size_key = "user_chars"
    gen = spec.load_generator(traffic["generator"])
    seconds = 30

    def work(seed):
        win = [a for a in gen.plan(traffic, seed, seconds)["arrivals"]
               if a["phase"] == "window"]
        return (sorted(len(a["text"]) for a in win),
                sorted(a["max_new_tokens"] for a in win),
                [a["due"] for a in win])

    chars1, new1, due1 = work(1)
    chars2, new2, due2 = work(2 ** 31 + 7)
    assert chars1 == chars2 and new1 == new2
    assert due1 != due2
    n = round(traffic["rate_per_s"] * seconds)
    assert len(due1) == n
    assert due1[0] == 0.0 and all(0 <= d < seconds for d in due1)
    assert due1 == sorted(due1)
    lo, hi = traffic[size_key]["min"], traffic[size_key]["max"]
    assert lo <= chars1[0] and chars1[-1] <= hi
    med = statistics.median(chars1)
    assert abs(med - traffic[size_key]["median"]) <= 0.05 * med


def test_sessions_phases_turns_and_turn_gap():
    traffic = spec.load_traffic("chat")
    gen = spec.load_generator("sessions")
    plan = gen.plan(traffic, 9, 30)
    arr = plan["arrivals"]
    assert [a["phase"] for a in arr] == sorted(
        (a["phase"] for a in arr), key=["warm", "window", "cool"].index)
    assert arr[0]["due"] == -traffic["warm_s"]
    assert len(plan["assistants"]) == traffic["assistants"]
    turns, last, early = {}, {}, 0
    for a in arr:
        turns[a["sender"]] = turns.get(a["sender"], 0) + 1
        if a["sender"] in last and a["due"] - last[a["sender"]] < traffic[
                "turn_gap_s"]:
            early += 1
        last[a["sender"]] = a["due"]
        assert a["receiver"] in plan["assistants"]
    assert max(turns.values()) <= traffic["turns"]["max"]
    # no agent speaks again before a reply could be back and read
    assert traffic["turn_gap_s"] >= 10.0 and early == 0
    assert max(turns.values()) > 1
    # unique text per message
    assert len({a["text"] for a in arr}) == len(arr)
    new = sorted(a["max_new_tokens"] for a in arr if a["phase"] == "window")
    assert new[0] >= 8 and new[-1] <= 256
    assert abs(statistics.median(new) - 48) <= 3


def test_single_turn_sessions_share_nothing():
    """A mix with ``turns`` fixed at 1 is the document digest: every
    message from a fresh agent, as data for the same generator."""
    traffic = spec.load_traffic("digest", TINY)
    plan = spec.load_generator("sessions").plan(traffic, 4, 40)
    arr = plan["arrivals"]
    assert len({a["sender"] for a in arr}) == len(arr)
    assert all(8 <= a["max_new_tokens"] <= 12 for a in arr)
    heads = {a["text"][:32] for a in arr}
    assert len(heads) == len(arr)


@pytest.mark.parametrize("dist,u,want", [
    ({"dist": "lognormal", "median": 120, "sigma": 0.8}, 0.5, 120.0),
    ({"dist": "uniform", "min": 32, "max": 64}, 0.25, 40.0),
    ({"dist": "geometric", "mean": 6}, 0.5, 4.0),
    ({"dist": "exponential", "mean": 2.0}, 0.5, 1.3862943611198906),
    ({"dist": "fixed", "value": 1}, 0.9, 1.0),
    ({"dist": "lognormal", "median": 120, "sigma": 0.8, "max": 200}, 0.99,
     200),
])
def test_quantiles(dist, u, want):
    assert draws.quantile(dist, u) == pytest.approx(want)


def test_gap_offsets_span_the_window():
    import random

    off = draws.gap_offsets(200, 25.0, random.Random(3))
    gaps = [b - a for a, b in zip(off, off[1:])]
    assert off[0] == 0.0 and off[-1] < 25.0
    # exponential gaps: the standard deviation is about the mean
    mean = statistics.mean(gaps)
    assert 0.8 * mean < statistics.pstdev(gaps) < 1.2 * mean


def test_text_is_exact_and_unique():
    assert len(draws.text(137, "a")) == 137
    assert draws.text(137, "a") == draws.text(137, "a")
    assert draws.text(137, "a") != draws.text(137, "b")


def test_blocks_carry_the_same_work_for_every_seed():
    import random

    dist = {"dist": "lognormal", "median": 48, "sigma": 0.7, "min": 8,
            "max": 256}
    a = draws.grid(dist, 120, random.Random(1), block=12)
    b = draws.grid(dist, 120, random.Random(2), block=12)
    assert a != b and sorted(a) == sorted(b)
    sums = []
    for i in range(0, 120, 12):
        assert sorted(a[i:i + 12]) == sorted(b[i:i + 12])
        sums.append(sum(a[i:i + 12]))
    # dealt back and forth: no block is far from the mean
    assert max(sums) - min(sums) <= 0.16 * (sum(sums) / len(sums))
    # without blocks: one shuffle of the whole grid
    assert sorted(draws.grid(dist, 120, random.Random(1))) == sorted(a)


def test_blocks_limit_bursts_the_same_for_every_seed():
    """Stratified, not Poisson: a block of 12 arrivals spans the same time
    for every seed, and no block is far from a tenth of the window."""
    import random

    def spans(seed):
        off = draws.gap_offsets(120, 50.0, random.Random(seed), block=12)
        return [off[i + 12] - off[i] for i in range(0, 108, 12)]

    a, b = spans(1), spans(2 ** 31 + 11)
    assert a == pytest.approx(b)
    assert 4.5 < min(a) and max(a) < 6.0
    with pytest.raises(ValueError):
        draws.quantile({"dist": "sideways"}, 0.5)
