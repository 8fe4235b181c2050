"""``benchmark/harness/check.py``: the values it returns, written out, and
what it catches and where. And ``benchmark/calibrate_routing.py``, the
script behind ``PERF.md``'s readings on routed stacks (PR 29), held to the
reference whose equations it repeats."""

import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import check  # noqa: E402

TINY = ROOT / "tests" / "benchmark" / "tiny"
V = 512


def stack_of(cfg_name, params):
    """What ``check.logit_gaps`` reads of a stack."""
    return types.SimpleNamespace(
        cfg_file=json.loads((TINY / cfg_name).read_text()),
        lanes=[types.SimpleNamespace(params=params)])


def record(prompt_len, n, seed):
    rng = np.random.default_rng(seed)
    return {"prompt": rng.integers(3, V, prompt_len).tolist(),
            "tokens": rng.integers(3, V, n).tolist(), "resume_len": 0}


def test_a_dense_reference_gives_the_gaps_written_out_here():
    """The values are those of ``check.logit_gaps`` at PR 27 on these
    records: arbitrary tokens, so the gaps are a typical logit's distance
    from the maximum and not a served token's."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import decoder
    from benchmark.harness import spec
    from swarmdb_tpu.models import llama

    stack = stack_of("tiny.json", None)
    stack.lanes[0].params = llama.init_params(
        spec.model_config(stack.cfg_file), jax.random.PRNGKey(7),
        dtype=jnp.bfloat16)
    recs = [record(40, 12, 1), record(200, 30, 2), record(5, 3, 3)]
    np.testing.assert_allclose(check.logit_gaps(stack, recs, decoder),
                               OLD_GAPS, rtol=0, atol=2e-5)


OLD_GAPS = [4.003138065338135, 5.2220258712768555, 4.097012996673584]


def stub(off=None):
    """A reference of the contract's shape whose logits put the record's
    own token first by 1.0 everywhere, and another token first by
    ``off[position]`` at those positions."""
    def logits_at(params, dims, tokens, at):
        tokens, at = np.asarray(tokens), np.asarray(at)
        logits = np.zeros((len(at), V), np.float32)
        nxt = tokens[np.minimum(at + 1, len(tokens) - 1)]
        logits[np.arange(len(at)), nxt] = 1.0
        for pos, by in (off or {}).items():
            logits[at == pos, (nxt[at == pos] + 1) % V] = 1.0 + by
        return logits

    return types.SimpleNamespace(Q_BLOCK=64, dims=lambda cfg_file: {},
                                 logits_at=logits_at)


# prompt and reply lengths whose generated positions do not overlap:
# 39-50, 59-74, 79-86, 19-28
LENGTHS = ((40, 12), (60, 16), (80, 8), (20, 10))


def stub_gaps(reference, lengths=LENGTHS):
    recs = [record(p, n, i) for i, (p, n) in enumerate(lengths)]
    return check.logit_gaps(stack_of("tiny.json", None), recs, reference)


def test_a_reference_that_agrees_everywhere_reads_no_gap():
    assert stub_gaps(stub()) == [0.0] * 4


# position 39 predicts the first record's first token, 45 its seventh,
# 50 its last
@pytest.mark.parametrize("pos", [39, 45, 50])
def test_one_position_off_by_more_than_the_tolerance_is_read(pos):
    gaps = stub_gaps(stub({pos: 0.3}))
    assert gaps[0] == pytest.approx(0.3) and gaps[0] > check.LOGIT_TOL
    assert gaps[1:] == [0.0] * 3
    # the prompt's own positions and what follows the reply decide nothing
    assert stub_gaps(stub({38: 0.3, 51: 0.3})) == [0.0] * 4


def test_a_long_reply_is_compared_on_its_first_max_at_tokens():
    n = check.MAX_AT + 40
    lengths = ((10, n),)
    assert stub_gaps(stub({9 + check.MAX_AT - 1: 0.3}), lengths) == [
        pytest.approx(0.3)]
    assert stub_gaps(stub({9 + check.MAX_AT: 0.3}), lengths) == [0.0]


def test_the_sample_holds_the_longest_and_follows_the_seed():
    recs = [record(10 + i, 5, i) for i in range(20)] + [
        dict(record(10, 0, 99), tokens=[])]
    longest = max(recs, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    a = check.sample(list(recs), 2 ** 31 + 11, 4)
    assert len(a) == 4 and a[0] is longest and all(r["tokens"] for r in a)
    assert a == check.sample(list(reversed(recs)), 2 ** 31 + 11, 4)
    assert a != check.sample(list(recs), 7, 4)
    assert check.sample(recs[-1:], 7, 4) == []


def test_no_data_file_reaches_a_constant_of_the_check():
    """A tolerance, a count or the size of the sample is a constant of
    ``check.py`` or ``run.py``: nothing in ``check.py`` reads a key of a
    cell, a configuration or a mix to set one, and it names no family."""
    src = (ROOT / "benchmark" / "harness" / "check.py").read_text()
    assert "cfg_file" in src            # read for the reference's dims only
    assert src.count("cfg_file") == src.count("reference.dims(stack.cfg_file")
    for word in ("traffic", "serving", "environ", "moe", "expert",
                 "mixtral"):
        assert word not in src.split('"""', 2)[2], word


# ---- the script behind PERF.md's readings on routed stacks --------------

def tiny_moe(seed):
    """The calibration script's tiny stack in ``moe_decoder``'s layout."""
    import jax.numpy as jnp

    from benchmark import calibrate_routing as cal

    shape = cal.SHAPES["tiny-moe"]
    p, tokens = cal.draw(shape, seed)
    L, D = shape["L"], shape["D"]
    params = {"embed": p["embed"], "lm_head": p["lm_head"],
              "final_norm": jnp.ones((D,), jnp.bfloat16),
              "layers": {n: jnp.stack([lp[n] for lp in p["layers"]])
                         for n in p["layers"][0] if n != "bias"}}
    params["layers"]["attn_norm"] = params["layers"]["mlp_norm"] = jnp.ones(
        (L, D), jnp.bfloat16)
    cfg_file = json.loads((TINY / "tiny-moe.json").read_text())
    return shape, p, tokens, params, cfg_file


def test_the_scripts_margin_is_the_kth_less_the_next_router_logit():
    """Layer by layer against a few lines of numpy: the margin a position
    is called near a tie by is its k-th chosen router logit less its best
    unchosen one, in float32."""
    import jax
    import jax.numpy as jnp

    from benchmark import calibrate_routing as cal
    from benchmark.reference import moe_decoder

    shape, p, tokens, params, cfg_file = tiny_moe(11)
    dims = moe_decoder.dims(cfg_file)
    k = dims["top_k"]
    # with wo = 0 a layer's router sees the layer's own input: the numpy
    # below then needs no attention of its own
    params["layers"]["wo"] = jnp.zeros_like(params["layers"]["wo"])
    for lp in p["layers"]:
        lp["wo"] = jnp.zeros_like(lp["wo"])
    _, idx, margin = cal.make_forward(shape, "f32")(p, tokens, None)
    x = np.asarray(params["embed"][tokens], np.float32)
    for i in range(shape["L"]):
        lp = jax.tree.map(lambda a: a[i], params["layers"])
        h = x / np.sqrt((x * x).mean(-1, keepdims=True) + dims["eps"])
        r = h @ np.asarray(lp["router"], np.float32)
        top = np.sort(r, axis=-1)
        want = top[:, -k] - top[:, -k - 1]
        np.testing.assert_allclose(np.asarray(margin[i]), want, atol=2e-5)
        clear = want > 1e-3
        assert (want < 0.05).sum() >= 5 and clear.mean() > 0.9, "a dull seed"
        np.testing.assert_array_equal(
            np.sort(np.asarray(idx[i]), -1)[clear],
            np.sort(np.argsort(r, -1)[:, -k:], -1)[clear])
        x = np.asarray(moe_decoder.layer(jnp.asarray(x), lp, **dims))


def test_the_calibration_scripts_float32_run_is_the_reference():
    """Logits of ``calibrate_routing.py``'s ``f32`` run against
    ``moe_decoder.py`` on the same weights, and what the other runs are:
    forced to its own choices the float32 run is itself, and the bf16 run
    differs by bf16's noise and chooses otherwise only near a tie."""
    import jax.numpy as jnp

    from benchmark import calibrate_routing as cal
    from benchmark.reference import moe_decoder

    shape, p, tokens, params, cfg_file = tiny_moe(5)
    dims = moe_decoder.dims(cfg_file)
    got, idx, margin = cal.make_forward(shape, "f32")(p, tokens, None)
    want = moe_decoder.logits_at(params, dims, tokens,
                                 jnp.arange(len(tokens)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
    forced = cal.make_forward(shape, "forced")(p, tokens, idx)[0]
    np.testing.assert_allclose(np.asarray(forced), np.asarray(got),
                               atol=1e-5)
    lg16, idx16, _ = cal.make_forward(shape, "bf16")(p, tokens, None)
    flip = ~(np.sort(np.asarray(idx), -1)
             == np.sort(np.asarray(idx16), -1)).all(-1)        # [L, T]
    first = flip & ~np.concatenate([np.zeros_like(flip[:1]),
                                    np.logical_or.accumulate(flip)[:-1]])
    assert flip.mean() < 0.05
    assert (np.asarray(margin)[first] < 0.1).all()
    move = np.abs(np.asarray(lg16) - np.asarray(got)).max(-1)
    assert 0.01 < np.median(move) < 0.2
    out = cal.summary(shape, [cal.read_seed(shape, 5, {
        m: cal.make_forward(shape, m) for m in (
            "f32", "bf16", "int8kv", "bf16acc", "forced")})], lo=32)
    assert out["positions"] == shape["T"]
    assert set(out["gaps"]) == {str(m) for m in cal.MARGINS}
    assert set(out["all_positions"]) == set(cal.READINGS)
    assert set(out["statistics"]) == {"256", str(shape["T"] - 32)}


def test_a_statistic_separates_where_the_least_is_over_the_largest():
    """``statistics`` on readings made up here: a sound side with one
    position in ten off by 0.02, a degraded side with one in five off by
    0.06. The mean separates them by a factor of 6 in every stretch; the
    largest gap by 3; the readings that do not follow the choices are not
    told apart by either."""
    from benchmark import calibrate_routing as cal

    T, lo = 32 + 1024, 32
    sound = np.zeros((2, T), np.float32)
    sound[:, ::10] = 0.02
    worse = np.zeros((2, T), np.float32)
    worse[:, ::5] = 0.06
    jump = sound.copy()
    jump[:, 100::256] = 1.5                 # a changed choice's jump
    out = cal.statistics({
        "followed_bf16": sound, "followed_int8kv": worse,
        "followed_bf16acc": worse, "gap_bf16": jump,
        "gap_int8kv": jump + worse, "gap_bf16acc": jump + worse,
        "gap_forced": jump, "move_bf16": jump}, lo)
    assert set(out) == {"256", "512", "1024"}
    assert out["256"]["stretches"] == 8 and out["1024"]["stretches"] == 2
    for W, row in out.items():
        assert "gap_forced" not in row and "move_bf16" not in row
        ratio = row["followed_int8kv_least_over_sound_largest"]
        assert ratio["mean"] == pytest.approx(6.0, rel=0.1), W
        assert ratio["max"] == pytest.approx(3.0)
        assert ratio["differs"] == pytest.approx(2.0, rel=0.1)
        assert ratio["over_0.03"] is None      # the sound side reads none
        assert row["followed_bf16"]["mean"][2] == pytest.approx(0.002,
                                                                rel=0.1)
        assert row["gap_int8kv_least_over_sound_largest"]["max"] < 1.1
        assert row["gap_int8kv_least_over_sound_largest"]["mean"] < 3
