"""The fifth architecture as data (PR 50): ``nemotron-3-nano-30b-a3b`` at the
published widths through ``spec.model_config``; ``tiny-nemotron``, a
configuration file in the shape of the published ``nemotron_h``
``config.json`` with half of the experts held, its reference
``benchmark/reference/nemotron_h_decoder.py`` and a spec of its own
(``tiny/spec-nemotron.json``). The reference alone, choosing for itself,
against the program's float32 forward; following the program's report, the
same; ``harness/ssm_cost.py`` against counts written out by hand; the new
readers on fixtures; and a whole run of ``run.py`` on
``tiny-nemotron.chat``."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import spec, ssm_cost  # noqa: E402

TINY = ROOT / "tests" / "benchmark" / "tiny"
SPEC = TINY / "spec-nemotron.json"
PUBLISHED = ROOT / "benchmark" / "configs" / "nemotron-3-nano-30b-a3b.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")
CELL = "nemotron3-nano.chat"


def cfg_file():
    return json.loads((TINY / "tiny-nemotron.json").read_text())


def published():
    return json.loads(PUBLISHED.read_text())


# ------------------------------------------------------------ configuration


def test_the_published_file_builds_the_programs_configuration():
    f = published()
    cfg = spec.model_config(f)
    assert cfg.head_dim == f["head_dim"] == 128 != cfg.dim // cfg.n_heads
    assert (cfg.n_layers, cfg.n_ssm_layers, cfg.n_routed_layers,
            cfg.n_attn_layers) == (52, 23, 23, 6)
    assert [i for i, t in enumerate(cfg.layer_types)
            if t == "full_attention"] == [5, 12, 19, 26, 33, 42]
    assert (cfg.n_experts, cfg.experts_held, cfg.first_held_expert,
            cfg.experts_per_token) == (128, 16, 0, 6)
    assert cfg.vocab_size == 16384 == f["published"]["vocab_size"] // 8
    assert cfg.router == "sigmoid_bias" and not cfg.rope
    assert cfg.sublayers and cfg.stateful and cfg.ssm_inner == 4096
    assert cfg.ssm_conv_dim == 6144
    kinds = {"M": "mamba", "E": "moe", "*": "full_attention"}
    assert f["layer_types"] == f["program"]["layer_types"] == [
        kinds[c] for c in f["hybrid_override_pattern"]]
    # every program field that restates a published key restates it
    p = f["program"]
    for field, key in [("norm_eps", "norm_eps"),
                       ("n_experts", ("published", "n_routed_experts")),
                       ("n_experts_held", "n_routed_experts"),
                       ("first_held_expert", "first_held_expert"),
                       ("experts_per_token", "num_experts_per_tok"),
                       ("expert_ffn_dim", "moe_intermediate_size"),
                       ("shared_ffn_dim",
                        "moe_shared_expert_intermediate_size"),
                       ("routed_scaling_factor", "routed_scaling_factor"),
                       ("conv_taps", "conv_kernel"),
                       ("conv_bias", "use_conv_bias"),
                       ("attn_head_dim", "head_dim"),
                       ("ssm_heads", "mamba_num_heads"),
                       ("ssm_head_dim", "mamba_head_dim"),
                       ("ssm_state", "ssm_state_size"),
                       ("ssm_groups", "n_groups")]:
        want = f[key[0]][key[1]] if isinstance(key, tuple) else f[key]
        assert p[field] == want, field
    assert f["n_shared_experts"] == 1 and f["norm_topk_prob"] is True
    assert f["mlp_hidden_act"] == "relu2" and f["n_group"] == 1
    s = f["serving"]
    assert (s["page_size"], s["decode_chunk"], s["max_seq"],
            s["max_batch"]) == (16, 8, 4096, 32)


@pytest.mark.skipif(not CATALOG.is_file(), reason="no catalog here")
def test_the_published_file_holds_the_catalogs_numbers():
    """Every key of the catalog row's ``config`` under the same key; what
    differs is named under ``reduced`` in the file and in BENCHMARK.json,
    no width is among it, and the depth is whole."""
    row = next(json.loads(l) for l in CATALOG.read_text().splitlines()
               if json.loads(l)["name"]
               == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    f = published()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "nemotron-3-nano-30b-a3b")
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert f["source"] == row["source_url"] == entry["source"]
    differs = {k for k, v in row["config"].items() if f.get(k) != v}
    assert differs == {"n_routed_experts", "vocab_size"}
    assert differs == set(entry["reduced"]) == set(f["reduced"])
    assert {k: row["config"][k] for k in f["published"]} == f["published"]
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "nemotron-3-nano-30b-a3b", "chat-nemotron3", 1)


def test_the_mix_is_chat_but_for_its_rate():
    mix = json.loads((ROOT / "benchmark" / "traffic"
                      / "chat-nemotron3.json").read_text())
    assert set(mix) == {"base", "what", "knee_per_s", "rate_per_s",
                        "live_conversations"} and mix["base"] == "chat"
    assert mix["rate_per_s"] == pytest.approx(0.8 * mix["knee_per_s"])
    live = mix["live_conversations"]
    assert live % 12 == 0 and 0 <= live - 17 * mix["rate_per_s"] < 12


def test_the_cell_reports_what_it_can_and_no_other_families_metrics():
    c = spec.load_cell(str(ROOT / "BENCHMARK.json"), CELL)
    names = {m["name"] for m in c.per_layer}
    assert {"ssm_decode_step_roofline_share", "ssm_snapshot_evicted_share",
            "ssm_snapshot_pool_fill", "prefix_state_forgone_share",
            "moe_held_choice_share", "moe_dropped_share",
            "prefill_attn_roofline_share", "decode_attn_roofline_share",
            "session_boundary_idle_ms"} <= names
    assert not {"decode_step_roofline_share", "wave_rider_token_share",
                "mla_decode_step_roofline_share",
                "mla_decode_attn_roofline_share",
                "mla_prefill_attn_roofline_share"} & names


def test_the_tiny_file_holds_the_programs_tiny_nemotron_widths():
    from swarmdb_tpu.models.configs import get_config

    assert spec.model_config(cfg_file()) == get_config(
        "tiny-nemotron", first_held_expert=2, n_experts_held=4,
        state_snapshots=6)


def test_the_references_dimensions_come_from_the_published_keys():
    from benchmark.reference import nemotron_h_decoder as ref

    f = cfg_file()
    d = ref.dims(f)
    assert (d["pattern"], d["n_experts"], d["n_held"], d["first_held"],
            d["top_k"], d["scaling"], d["norm_topk"]) == (
        "MEMEM*EME", 8, 4, 2, 2, 2.5, True)
    assert (d["ssm_heads"], d["ssm_head_dim"], d["ssm_state"],
            d["ssm_groups"], d["taps"], d["conv_bias"]) == (8, 16, 16, 2, 4,
                                                            True)
    f.pop("program")           # nothing of the program group is read
    f.pop("layer_types")       # nor the derived list
    assert ref.dims(f) == d
    assert ref.FOLLOWS_ROUTING is True
    big = ref.dims(published())
    assert (len(big["pattern"]), big["pattern"].count("E"), big["n_experts"],
            big["n_held"], big["head_dim"], big["n_kv_heads"]) == (
        52, 23, 128, 16, 128, 2)


# ------------------------------------------------ reference against program


@pytest.fixture(scope="module")
def both():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import nemotron_h_decoder as ref
    from swarmdb_tpu.models import llama, nemotron_h

    f = cfg_file()
    cfg = spec.model_config(f)
    params = nemotron_h.init_params(cfg, jax.random.PRNGKey(7), jnp.float32)
    T = ref.Q_BLOCK
    tokens = jax.random.randint(jax.random.PRNGKey(1), (T,), 3,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want, _cache, routing = llama.forward(
            params, cfg, tokens[None], jnp.arange(T)[None],
            llama.init_kv_cache(cfg, 1, T, jnp.float32))
    return ref, ref.dims(f), params, tokens, np.asarray(want[0]), routing[0]


def test_the_reference_alone_matches_the_programs_float32_forward(both):
    """Its own recurrence, conv, gated group norm, attention without RoPE
    over heads wider than the hidden size's share, its own sigmoid top-k,
    gates and share of the experts: all of the program's are compared."""
    import jax.numpy as jnp

    ref, dims, params, tokens, want, _routing = both
    at = jnp.arange(len(tokens))
    got = np.asarray(ref.logits_at(params, dims, tokens, at))
    assert np.abs(got - want).max() < 3e-4
    for wrong in (dict(first_held=0), dict(scaling=1.0),
                  dict(norm_topk=False), dict(conv_bias=False),
                  dict(eps=1e-2)):
        other = np.asarray(ref.logits_at(params, dict(dims, **wrong),
                                         tokens, at))
        assert np.abs(other - want).max() > 1e-3, wrong


def test_the_reference_following_the_programs_report_matches_too(both):
    import jax.numpy as jnp

    ref, dims, params, tokens, want, routing = both
    r = np.asarray(routing)
    assert r.shape == (len(tokens), 4, 2)       # the 4 E layers, in order
    assert (r < 0).any() and (r >= 0).any()     # half of the experts held
    at = jnp.arange(len(tokens))
    got = np.asarray(ref.logits_at(params, dims, tokens, at, routing))
    assert np.abs(got - want).max() < 3e-4
    flipped = jnp.where(routing < 0, ~routing, routing)
    other = np.asarray(ref.logits_at(params, dims, tokens, at,
                                     jnp.roll(flipped, 1, axis=-1) ^ 1))
    assert np.abs(other - want).max() > 1e-3
    with pytest.raises(ValueError, match="layers that route"):
        ref.logits_at(params, dims, tokens, jnp.arange(4), routing[:, :2])


def test_a_file_that_disagrees_with_the_weights_is_an_error(both):
    import jax.numpy as jnp

    ref, dims, params, tokens, _want, _routing = both
    for wrong, match in ((dict(pattern="MEMEM*EMM"), "layer 8"),
                         (dict(pattern="MEMEM*EM"), "more layers"),
                         (dict(n_held=8), "8 experts are held"),
                         (dict(n_experts=32), "32 routed experts")):
        with pytest.raises(ValueError, match=match):
            ref.logits_at(params, dict(dims, **wrong), tokens, jnp.arange(4))


# --------------------------------------------------------------------- cost


def test_ssm_cost_against_counts_written_out_by_hand():
    f = published()
    assert ssm_cost.kinds(f) == {"M": 23, "E": 23, "*": 6}
    assert ssm_cost.mixer(f) == (64, 64, 8, 128, 6144)
    # a layer keeps S [64, 64, 128] and 3 rows of 6144
    assert ssm_cost.state_values(f) == 524288 + 18432
    # ISSUE 50: 23 x 524,288 values are 24.1 MB in bf16, 0.85 MB of conv
    assert ssm_cost.state_bytes(f) == 23 * 542720 * 2 == 24965120
    # W_in 2688 x 10304, W_out 4096 x 2688, conv 6144 x (4 + 1), 3 x 64, norm
    w = ssm_cost.mixer_weights(f)
    assert w == 2688 * 10304 + 4096 * 2688 + 6144 * 5 + 192 + 4096
    assert 38.7e6 < w < 38.8e6                  # ISSUE 50: 38.74M
    # one decode step of one layer, 10 live rows, chunks of 8
    flops, moved = ssm_cost.step(f, 10, 8)
    assert flops == 10 * (2 * w + 4 * 524288)
    assert moved == 2 * (w + 10 * 542720 * 1.125)
    # a wave of rows of 200 and 50 new tokens that emits one snapshot
    flops, moved = ssm_cost.scan(f, [200, 50], 1)
    assert flops == 4 * 250 * 524288
    assert moved == 2 * (250 * (6144 + 64 + 4096) + 5 * 542720)
    cost = ssm_cost.weights(f)
    assert cost["expert"] == 2 * 2688 * 1856 * 2      # two matrices
    assert (cost["routed_layers"], cost["held"], cost["scored"],
            cost["top_k"]) == (23, 16, 128, 6)
    attn = 2688 * 4096 * 2 + 2 * 2688 * 256
    fixed = (23 * w + 6 * attn + 23 * (2688 * 128 + 128 + 2 * 2688 * 3712)
             + 52 * 2688 + 2688 + 16384 * 2688)
    assert cost["fixed"] == 2 * fixed
    assert 3.0e9 < cost["fixed"] < 3.2e9        # ISSUE 50: 3.1 GB a step
    # 1,000 steps of 10 rows that hit 6 of 16 experts a layer
    flops, moved = ssm_cost.decode_steps(
        f, 1000, 1000 * 23 * 6, 10000 * 23 * 6 / 8, 10000, 5e8, 8)
    assert moved == pytest.approx(
        1000 * cost["fixed"] + 138000 * cost["expert"] + 5e8
        + 10000 * 24965120 * 1.125)
    # the state is a twentieth of such a step, the Mamba-2 layers a third
    assert 0.03 < 10000 * 24965120 * 1.125 / moved < 0.08
    assert 0.25 < (1000 * 23 * w * 2 + 10000 * 24965120 * 1.125) / moved < 0.5


# ------------------------------------------------------------------ readers


def ctx_of(**more):
    f = published()
    ctx = {"config": f, "model": spec.model_config(f), "notes": {},
           "page_size": 16, "decode_chunk": 8,
           "device_kind": "TPU v5 lite", "trace_span": (100.0, 110.0),
           "trace_counters": {}, "counters": {}, "rows": [],
           "engine_records": {}, "trace": None}
    ctx.update(more)
    return ctx


def test_the_snapshot_readers_read_their_counters():
    evicted = spec.load_reader("ssm_snapshot_evicted_share").read
    fill = spec.load_reader("ssm_snapshot_pool_fill").read
    c = {"ssm_snapshots_taken": 200, "ssm_snapshots_evicted": 50,
         "ssm_snapshot_slots": 8000, "ssm_snapshot_slots_live": 6000}
    assert evicted(ctx_of(counters=c)) == 25.0
    assert fill(ctx_of(counters=c)) == 75.0
    # a pool that evicted nothing reads 0, not nothing
    assert evicted(ctx_of(counters=dict(c, ssm_snapshots_evicted=0))) == 0.0
    # a program without snapshots (the parent) writes neither
    for read in (evicted, fill):
        assert read(ctx_of()) is None
        assert read(ctx_of(counters={"prefix_reused_tokens": 5})) is None
    forgone = spec.load_reader("prefix_state_forgone_share").read
    assert forgone(ctx_of(counters={"prefix_state_forgone_tokens": 100,
                                    "prefix_reused_tokens": 300})) == 25.0


def records():
    # two requests decoding all through the span at ~1,000 tokens
    return {i: {"first_t": 90.0, "last_t": 120.0, "n_tokens": 301,
                "prompt": [0] * 850} for i in "ab"}


def test_the_ssm_decode_step_reader_counts_what_a_step_must_move():
    read = spec.load_reader("ssm_decode_step_roofline_share").read
    f = published()
    w = ssm_cost.weights(f)
    steps = 1000
    counters = {"moe_expert_step_slots": steps * 23 * 16,
                "moe_expert_hits": steps * 23 * 5,
                "moe_assignments": 80000, "moe_held_assignments": 10000}
    trace = {"kernels": {}, "programs": {
        "_decode_resident_greedy": {"busy_s": 9.0, "span_s": 9.5,
                                    "calls": 100},
        "_prefill_ragged_insert": {"busy_s": 2.0, "span_s": 2.0,
                                   "calls": 50}}}
    ctx = ctx_of(trace=trace, trace_counters=counters,
                 engine_records=records())
    got = read(ctx)
    note = ctx["notes"]["ssm_decode_step_roofline_share"]
    assert note["steps"] == steps and note["decode_s"] == 9.0
    assert note["held_experts_hit_a_step_a_layer"] == 5
    assert note["expert_share"] == 5 / 16
    assert note["row_steps"] == pytest.approx(200)    # 2 rows x 100 steps
    expected = (steps * (w["fixed"] + 23 * 5 * w["expert"])
                + 200 * ssm_cost.state_bytes(f) * 1.125)
    assert expected < note["bytes"] < expected * 1.01   # + keys and values
    assert got == pytest.approx(100 * note["least_s"] / 9.0)
    assert 0 < got < 100
    assert read(ctx_of(trace=trace)) is None              # no counters
    assert read(ctx_of(trace=None, trace_counters=counters)) is None
    # another family's configuration file reads nothing
    other = json.loads((ROOT / "benchmark" / "configs"
                        / "lfm2-8b-a1b.json").read_text())
    assert read(ctx_of(trace=trace, trace_counters=counters,
                       config=other)) is None


# ---------------------------------------------------------------- whole runs


@pytest.fixture(scope="module")
def xla_cache(tmp_path_factory):
    return tmp_path_factory.mktemp("xla")


def whole_run(tmp_path, xla_cache, seed, trace=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(xla_cache))
    env.pop("XLA_FLAGS", None)
    run_py = str(ROOT / "benchmark" / "run.py")
    argv = [run_py, "--spec", str(SPEC), "--workload", "tiny-nemotron.chat",
            "--platform", "cpu", "--seed", str(seed), "--seconds", "3",
            "--trace", str(trace)]
    proc = subprocess.run([sys.executable, *argv], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2]), proc.stderr


def test_the_replay_of_a_plan_sizes_the_snapshot_pool():
    """``scripts/replay_snapshot_pool.py`` over the cell's own mix: a pool
    for every conversation of a run forgoes nothing, and under a pool
    smaller than the live conversations the shallowest to leave costs
    fewer tokens than the least recently used or the newest."""
    sys.path.insert(0, str(ROOT / "scripts"))
    from replay_snapshot_pool import replay

    mine = json.loads((ROOT / "benchmark/traffic/chat-nemotron3.json"
                       ).read_text())
    params = json.loads((ROOT / "benchmark/traffic" / (
        mine.pop("base") + ".json")).read_text())
    params.update(mine)
    assert replay(params, 7, 50.0, 400, "shallowest") == (0.0, 0)
    share = {rule: replay(params, 7, 50.0, 89, rule)[0]
             for rule in ("shallowest", "newest", "lru")}
    assert 0 < share["shallowest"] < share["newest"] < share["lru"] < 100


def test_a_whole_tiny_nemotron_run_ends_in_the_contract_line(tmp_path,
                                                             xla_cache):
    """Traced, so that the readers run: on the CPU there is no device
    trace and the roofline reader returns nothing and raises nothing; the
    counter readers read. Six snapshot slots under the chat mix's
    conversations: resumed turns, evicted snapshots and forgone hits are
    all among the checked records' histories."""
    out, facts, err = whole_run(tmp_path, xla_cache, 2 ** 31 + 11, trace=1)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert facts["reference"] == "benchmark/reference/nemotron_h_decoder.py"
    assert facts["routing_followed"] is True
    assert facts["logit_gaps"] and max(facts["logit_gaps"]) <= facts[
        "logit_tol"]
    assert "correct True" in err.strip().splitlines()[-1]
    m = out["metrics"]
    assert m["moe_dropped_share"]["value"] == 0.0
    assert 20.0 < m["moe_held_choice_share"]["value"] < 80.0   # 50 is even
    assert m["prefix_hit_share"]["value"] > 0
    assert 0 < m["ssm_snapshot_pool_fill"]["value"] <= 100.0
    assert 0 <= m["ssm_snapshot_evicted_share"]["value"] <= 100.0
    assert "prefix_state_forgone_share" in m
    assert "ssm_decode_step_roofline_share" not in m
    c = facts["counters_window"]
    assert 0 < c["moe_held_assignments"] < c["moe_assignments"]
    assert c["ssm_state_tokens_resumed"] == c["prefix_reused_tokens"] > 0
    assert c["ssm_snapshots_taken"] > 0 and c["wave_rider_tokens"] == 0
    assert 0 < c["moe_expert_hits"] <= c["moe_expert_step_slots"]
