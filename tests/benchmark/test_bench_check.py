"""``benchmark/harness/check.py``: the values it returns, written out,
what it catches and where, and what it hands a reference that follows the
program's routing. And ``benchmark/calibrate_routing.py``, the script
behind ``PERF.md``'s readings on routed stacks (PR 29), held to the
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


# ---- a reference that follows the program's routing ----------------------

L_ROUTED, K = 3, 2


def following_stub(seen):
    """A stub that declares ``FOLLOWS_ROUTING`` and keeps what it was
    handed."""
    ref = stub()
    plain = ref.logits_at

    def logits_at(params, dims, tokens, at, routing=None):
        seen.append(None if routing is None else np.asarray(routing))
        return plain(params, dims, tokens, at)

    ref.logits_at, ref.FOLLOWS_ROUTING = logits_at, True
    return ref


def routed_record(prompt_len, n, seed, rows=None):
    """A record as the ``Recorder`` leaves it for a request of a
    configuration that routes: a row a position that went through the
    stack, and one more where the reply ended on ``eos``."""
    rec = record(prompt_len, n, seed)
    rows = prompt_len + n - 1 if rows is None else rows
    rng = np.random.default_rng(seed)
    routing = rng.integers(0, 8, (rows, L_ROUTED, K)).astype(np.int16)
    routing[rng.random(routing.shape) < 0.2] ^= -1      # ~e: dropped
    return dict(rec, routing=routing, routing_complete=True)


# (prompt, reply, rows the record holds): the usual record, one with a
# row more than the check needs, one longer than MAX_AT compares
@pytest.mark.parametrize("p, n, rows", [
    (40, 12, 51), (60, 16, 76), (10, check.MAX_AT + 40, None)])
def test_a_following_reference_gets_the_rows_cut_and_padded(p, n, rows):
    seen = []
    rec = routed_record(p, n, 5, rows)
    gaps = check.logit_gaps(stack_of("tiny.json", None), [rec],
                            following_stub(seen))
    assert gaps == [0.0] and len(seen) == 1
    got, = seen
    need = p + min(n, check.MAX_AT) - 1
    assert got.dtype == np.int16
    assert got.shape == (-(-(need + 1) // 64) * 64, L_ROUTED, K)
    np.testing.assert_array_equal(got[:need], rec["routing"][:need])
    # the last compared token and the padding take nothing: every choice
    # reads as left out, whatever expert it names
    assert (got[need:] == ~0).all() and (got[need:] < 0).all()


@pytest.mark.parametrize("fault", ["none", "incomplete", "short"])
def test_a_record_that_cannot_be_followed_raises(fault):
    """Never a fall back to the unforced comparison: a dense request (no
    routing), a context that came by a path that carries none (a rolling
    resume, a promoted tier), a record a row short."""
    rec = routed_record(40, 12, 5)
    if fault == "none":
        rec["routing"], rec["routing_complete"] = None, False
    elif fault == "incomplete":
        rec["routing_complete"] = False
    else:
        rec["routing"] = rec["routing"][:40 + 12 - 2]
    seen = []
    with pytest.raises(ValueError, match="routing"):
        check.logit_gaps(stack_of("tiny.json", None), [rec],
                         following_stub(seen))
    assert seen == []


def test_a_reference_that_does_not_follow_never_sees_the_routing():
    """Four arguments, as before PR 35, whatever the record holds."""
    calls = []
    ref = stub()
    plain = ref.logits_at

    def logits_at(*args, **kw):
        calls.append((len(args), sorted(kw)))
        return plain(*args)

    ref.logits_at = logits_at
    assert not check.follows_routing(ref)
    recs = [routed_record(40, 12, 5), record(60, 16, 1),
            dict(record(20, 10, 2), routing=None, routing_complete=False)]
    assert check.logit_gaps(stack_of("tiny.json", None), recs, ref) == [
        0.0] * 3
    assert calls == [(4, [])] * 3


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


def test_the_calibration_scripts_float32_run_is_the_reference():
    """Logits of ``calibrate_routing.py``'s ``f32`` run against
    ``moe_decoder.py`` on the same weights, and what the other runs are:
    forced to its own choices the float32 run is itself, and the bf16 run
    differs by bf16's noise and chooses otherwise at a few positions in a
    hundred."""
    import jax.numpy as jnp

    from benchmark import calibrate_routing as cal
    from benchmark.reference import moe_decoder

    shape, p, tokens, params, cfg_file = tiny_moe(5)
    dims = moe_decoder.dims(cfg_file)
    got, idx = cal.make_forward(shape, "f32")(p, tokens, None)
    want = moe_decoder.logits_at(params, dims, tokens,
                                 jnp.arange(len(tokens)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-4, rtol=2e-4)
    forced = cal.make_forward(shape, "forced")(p, tokens, idx)[0]
    np.testing.assert_allclose(np.asarray(forced), np.asarray(got),
                               atol=1e-5)
    lg16, idx16 = cal.make_forward(shape, "bf16")(p, tokens, None)
    flip = ~(np.sort(np.asarray(idx), -1)
             == np.sort(np.asarray(idx16), -1)).all(-1)        # [L, T]
    assert 0 < flip.mean() < 0.05
    move = np.abs(np.asarray(lg16) - np.asarray(got)).max(-1)
    assert 0.01 < np.median(move) < 0.2
    out = cal.summary(shape, [cal.read_seed(shape, 5, {
        m: cal.make_forward(shape, m) for m in (
            "f32", "bf16", "int8kv", "bf16acc", "forced")})], lo=32)
    assert out["positions"] == shape["T"]
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
