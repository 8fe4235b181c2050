"""``scripts/routing_follow_drill.py`` through the engine on the CPU
(ISSUE 33): on ``tiny-moe.chat``, a float32 follower FORCED to the routing
the engine reported reads at most ``LOGIT_TOL`` on every generated
position of every sampled record, where the same follower left to its own
top-k reads over it on some seeds with nothing wrong (PR 29's failures);
and with the sampler broken under the engine the forced reading still
fails, so following does not hide a fault.

A seed is a stack of its own (its weights come from the seed) and half a
minute of tracing, so of PR 29's twelve seeds tier-1 holds the four that
say something: the three that read over the tolerance unforced, and the
one whose forced reading is the largest. The script runs all twelve
(``--seeds`` defaults to them; PERF.md section 6 has the readings)."""

import functools
import importlib.util
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SPEC = os.path.join(ROOT, "tests", "benchmark", "tiny", "spec.json")


@functools.lru_cache(maxsize=None)
def _drill():
    spec = importlib.util.spec_from_file_location(
        "routing_follow_drill",
        os.path.join(ROOT, "scripts", "routing_follow_drill.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, mod._follower()


@functools.lru_cache(maxsize=None)
def _line(seed):
    """One seed's window and comparison (about half a minute)."""
    from benchmark.harness import spec as specs

    drill, pieces = _drill()
    return drill.run_seed(specs.load_cell(SPEC, "tiny-moe.chat"), seed, 3.0,
                          pieces)


SEEDS = (2147483659, 3000000019, 1000003, 77, 424243, 2900000001,
         2900000002, 31337, 1234567891, 99991, 808080808, 58)


def test_the_drills_seeds_are_pr29s():
    assert _drill()[0].PR29_SEEDS == SEEDS


def check_forced(line):
    assert line["window_replied"] == line["window_messages"] > 0
    assert len(line["records"]) == 4
    for rec in line["records"]:
        # one row for every position that went through the stack: all
        # but the last sampled token, which on "eos" is the eos itself
        assert rec["routing_rows"] == (rec["prompt"] + rec["generated"]
                                       - (rec["reason"] != "eos"))
        assert rec["routing_complete"] is True
        assert rec["forced_over_tol"] == 0
        assert rec["forced_max"] <= line["logit_tol"]
    # records whose prefix came from the cache are among the sampled
    assert line["counters"]["prefix_reused_tokens"] > 0
    assert line["counters"]["routing_incomplete_requests"] == 0
    assert line["counters"]["moe_assignments"] > 0


# read over the tolerance unforced (0.126, 0.151, 0.914 in PR 29 and now)
UNFORCED_FAIL = (3000000019, 1000003, 31337)
# and the largest forced reading of the twelve (0.031)
HELD = UNFORCED_FAIL + (2147483659,)


@pytest.mark.parametrize("seed", HELD)
def test_forced_to_the_engines_routing_every_position_is_within_tol(seed):
    assert seed in SEEDS
    check_forced(_line(seed))


def test_unforced_the_same_follower_fails_sound_runs():
    """PR 29's finding, through the engine: the follower left to its own
    float32 top-k reads over the tolerance on seeds where nothing is
    wrong (0.126, 0.151 and 0.914, then and now), and within it once
    forced."""
    for seed in UNFORCED_FAIL:
        line = _line(seed)
        assert line["unforced_max"] > line["logit_tol"]
        assert line["forced_max"] <= line["logit_tol"]


def test_a_broken_sampler_still_fails_forced(monkeypatch):
    """PR 29's control: every decode program takes the second-best token.
    The routing the engine reports is then the routing of the tokens it
    fed, and the follower, forced to it, still reads the fault."""
    import jax.numpy as jnp

    import swarmdb_tpu.backend.engine as engine
    from benchmark.harness import spec as specs

    drill, pieces = _drill()
    monkeypatch.setattr(
        engine, "sample_tokens",
        lambda logits, *a, **k: jnp.argsort(logits, axis=-1)[:, -2].astype(
            jnp.int32))
    line = drill.run_seed(specs.load_cell(SPEC, "tiny-moe.chat"), SEEDS[3],
                          3.0, pieces)
    assert line["forced_max"] > line["logit_tol"]
    assert all(r["forced_over_tol"] > 0 for r in line["records"])
