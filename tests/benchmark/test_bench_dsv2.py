"""The fourth architecture as data (PR 44): ``deepseek-v2`` at the
published widths through ``spec.model_config``; ``tiny-dsv2``, a
configuration file in the shape of the published ``deepseek_v2``
``config.json`` with one routing group of four held, its reference
``benchmark/reference/deepseek_v2_decoder.py`` and a spec of its own
(``tiny/spec-dsv2.json``). The reference alone, choosing for itself, against
the program's float32 forward; following the program's report, the same;
``harness/mla_cost.py`` against counts written out by hand; the new
readers on fixtures; and a whole run of ``run.py`` on ``tiny-dsv2.chat``."""

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import mla_cost, spec  # noqa: E402

TINY = ROOT / "tests" / "benchmark" / "tiny"
SPEC = TINY / "spec-dsv2.json"
PUBLISHED = ROOT / "benchmark" / "configs" / "deepseek-v2.json"
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


def cfg_file():
    return json.loads((TINY / "tiny-dsv2.json").read_text())


def published():
    return json.loads(PUBLISHED.read_text())


# ------------------------------------------------------------ configuration


def test_the_published_file_builds_the_programs_configuration():
    f = published()
    cfg = spec.model_config(f)
    assert cfg.head_dim == f["head_dim"] == 192
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_routed_layers) == (
        f["num_hidden_layers"], 1, f["num_hidden_layers"] - 1)
    assert f["num_hidden_layers"] >= 1 + 4
    assert (cfg.n_experts, cfg.experts_held, cfg.first_held_expert) == (
        160, 20, 0)
    assert (cfg.n_group, cfg.topk_group, cfg.experts_per_token) == (8, 3, 6)
    assert cfg.vocab_size == 12800 == f["published"]["vocab_size"] // 8
    assert cfg.router == "softmax_group_limited"
    # every program field that restates a published key restates it
    rs, p = f["rope_scaling"], f["program"]
    assert (p["yarn_factor"], p["yarn_original_max_seq"], p["yarn_mscale"],
            p["yarn_mscale_all_dim"], p["yarn_beta_fast"],
            p["yarn_beta_slow"]) == (
        rs["factor"], rs["original_max_position_embeddings"], rs["mscale"],
        rs["mscale_all_dim"], rs["beta_fast"], rs["beta_slow"])
    for field, key in [("q_lora_rank", "q_lora_rank"),
                       ("kv_lora_rank", "kv_lora_rank"),
                       ("qk_nope_head_dim", "qk_nope_head_dim"),
                       ("qk_rope_head_dim", "qk_rope_head_dim"),
                       ("v_head_dim", "v_head_dim"),
                       ("n_experts", "n_routed_experts"),
                       ("experts_per_token", "num_experts_per_tok"),
                       ("n_dense_layers", "first_k_dense_replace"),
                       ("expert_ffn_dim", "moe_intermediate_size"),
                       ("n_group", "n_group"), ("topk_group", "topk_group"),
                       ("routed_scaling_factor", "routed_scaling_factor"),
                       ("shared_experts", "n_shared_experts"),
                       ("first_held_expert", "first_held_expert"),
                       ("n_experts_held", "n_routed_experts_held")]:
        assert p[field] == f[key], field


@pytest.mark.skipif(not CATALOG.is_file(), reason="no catalog here")
def test_the_published_file_holds_the_catalogs_numbers():
    """Every key of the catalog row's ``config`` under the same key; what
    differs is named under ``reduced`` in the file and in BENCHMARK.json,
    and no width is among it."""
    row = next(json.loads(l) for l in CATALOG.read_text().splitlines()
               if json.loads(l)["name"] == "DeepSeek-V2")
    f = published()
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "deepseek-v2")
    assert f["source"] == row["source_url"] == entry["source"]
    differs = {k for k, v in row["config"].items() if f.get(k) != v}
    assert differs == {"num_hidden_layers", "vocab_size"}
    assert differs <= set(entry["reduced"]) == set(f["reduced"])
    assert {k: row["config"][k] for k in f["published"]} == f["published"]


def test_the_tiny_file_holds_the_programs_tiny_dsv2_widths():
    from swarmdb_tpu.models.configs import get_config

    assert spec.model_config(cfg_file()) == get_config(
        "tiny-dsv2", first_held_expert=4, n_experts_held=4)


def test_the_references_dimensions_come_from_the_published_keys():
    from benchmark.reference import deepseek_v2_decoder as ref

    f = cfg_file()
    d = ref.dims(f)
    assert (d["n_experts"], d["top_k"], d["n_group"], d["topk_group"],
            d["first_held"], d["n_held"], d["n_dense"]) == (16, 4, 4, 2, 4,
                                                            4, 1)
    assert d["yarn"] == (4.0, 64, 32.0, 1.0, 0.707, 0.707)
    f.pop("program")           # nothing of the program group is read
    assert ref.dims(f) == d
    assert ref.FOLLOWS_ROUTING is True
    big = ref.dims(published())
    assert (big["n_experts"], big["n_held"], big["kv_rank"], big["nope"],
            big["rope"]) == (160, 20, 512, 128, 64)


# ------------------------------------------------ reference against program


@pytest.fixture(scope="module")
def both():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import deepseek_v2_decoder as ref
    from swarmdb_tpu.models import deepseek

    f = cfg_file()
    cfg = spec.model_config(f)
    params = deepseek.init_params(cfg, jax.random.PRNGKey(7), jnp.float32)
    T = ref.Q_BLOCK
    tokens = jax.random.randint(jax.random.PRNGKey(1), (T,), 3,
                                cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        want, _rows, routing = deepseek.forward(
            params, cfg, tokens[None], jnp.arange(T)[None])
    return ref, ref.dims(f), params, tokens, np.asarray(want[0]), routing[0]


def test_the_reference_alone_matches_the_programs_float32_forward(both):
    """Its own group-limited top-k and gates, its own YaRN, its expanded
    attention: the program's router, scale, norms and share of the
    experts are all compared."""
    import jax.numpy as jnp

    ref, dims, params, tokens, want, _routing = both
    at = jnp.arange(len(tokens))
    got = np.asarray(ref.logits_at(params, dims, tokens, at))
    assert np.abs(got - want).max() < 2e-4
    # another share of the experts, another scale, plain RoPE: each reads
    # otherwise
    for wrong in (dict(first_held=0), dict(scaling=1.0),
                  dict(yarn=(1.0, 64, 32.0, 1.0, 0.707, 0.707)),
                  dict(topk_group=4)):
        other = np.asarray(ref.logits_at(params, dict(dims, **wrong),
                                         tokens, at))
        assert np.abs(other - want).max() > 1e-2, wrong


def test_the_reference_following_the_programs_report_matches_too(both):
    import jax.numpy as jnp

    ref, dims, params, tokens, want, routing = both
    r = np.asarray(routing)
    assert r.shape == (len(tokens), 3, 4)
    assert (r < 0).any() and (r >= 0).any()      # one group of four held
    at = jnp.arange(len(tokens))
    got = np.asarray(ref.logits_at(params, dims, tokens, at, routing))
    assert np.abs(got - want).max() < 2e-4
    # a report that names other experts as held reads otherwise
    flipped = jnp.where(routing < 0, ~routing, routing)
    other = np.asarray(ref.logits_at(params, dims, tokens, at,
                                     jnp.roll(flipped, 1, axis=-1) ^ 1))
    assert np.abs(other - want).max() > 1e-2
    with pytest.raises(ValueError, match="layers that route"):
        ref.logits_at(params, dims, tokens, jnp.arange(4), routing[:, :2])


def test_a_file_that_disagrees_with_the_weights_is_an_error(both):
    import jax.numpy as jnp

    ref, dims, params, tokens, _want, _routing = both
    for wrong, match in ((dict(n_dense=2), "layer 1"),
                         (dict(n_layers=5), "4 layers in the weights"),
                         (dict(n_held=8), "8 experts are held"),
                         (dict(n_experts=32), "32 routed experts")):
        with pytest.raises(ValueError, match=match):
            ref.logits_at(params, dict(dims, **wrong), tokens, jnp.arange(4))


# --------------------------------------------------------------------- cost


def test_mla_cost_against_counts_written_out_by_hand():
    f = published()
    assert mla_cost.row(f) == (128, 576, 512)
    # a decode row at a context of 1,000: 2 * 128 * (576 + 512) a token
    flops, moved = mla_cost.absorbed_decode(f, [1000])
    assert flops == 2 * 128 * 1088 * 1000 == 278_528_000
    assert moved == 2 * (1000 * 576 + 128 * 1088) == 1_430_528
    # a wave: 200 new on 800 cached and a cold row of 10
    flops, moved = mla_cost.absorbed_prefill(f, [(800, 200), (0, 10)])
    pairs = 200 * 800 + 200 * 201 // 2 + 10 * 11 // 2
    assert pairs == 180_155
    assert flops == 2 * 128 * 1088 * pairs
    assert moved == 2 * ((1000 + 10) * 576 + 210 * 128 * 1088)
    w = mla_cost.weights(f)
    attn = (5120 * 1536 + 1536 + 1536 * 128 * 192 + 5120 * 576 + 512
            + 512 * 128 * 256 + 128 * 128 * 5120)
    assert attn == 149_227_520
    n = f["num_hidden_layers"]
    fixed = (n * (attn + 2 * 5120) + 5120 + 3 * 5120 * 12288
             + (n - 1) * (5120 * 160 + 3 * 5120 * 1536 * 2)
             + 12800 * 5120)
    assert w["fixed"] == 2 * fixed
    assert w["expert"] == 2 * 3 * 5120 * 1536 == 47_185_920
    assert (w["routed_layers"], w["held"], w["top_k"]) == (n - 1, 20, 6)
    # 10 steps, 300 (step, layer, expert) hits, 400 held choices, 80
    # row-steps, 1 MB of latent traffic
    flops, moved = mla_cost.decode_steps(f, 10, 300, 400, 80, 1e6)
    assert moved == 10 * w["fixed"] + 300 * w["expert"] + 1e6
    assert flops == 2.0 * (80 * fixed + 400 * 3 * 5120 * 1536)


# ------------------------------------------------------------------ readers


def ctx_of(**more):
    f = published()
    ctx = {"config": f, "model": None, "notes": {}, "page_size": 16,
           "device_kind": "TPU v5 lite", "trace_span": (100.0, 110.0),
           "trace_counters": {}, "counters": {}, "rows": [],
           "engine_records": {}, "trace": None}
    ctx.update(more)
    return ctx


def test_moe_held_choice_share_reads_the_two_counters():
    read = spec.load_reader("moe_held_choice_share").read
    assert read(ctx_of(counters={"moe_assignments": 4800,
                                 "moe_held_assignments": 600})) == 12.5
    # a program that holds every expert writes no such counter
    assert read(ctx_of(counters={"moe_assignments": 4800})) is None
    assert read(ctx_of()) is None


def records():
    # two requests decoding all through the span at ~1,000 tokens
    return {i: {"first_t": 90.0, "last_t": 120.0, "n_tokens": 301,
                "prompt": [0] * 850} for i in "ab"}


def test_the_mla_decode_reader_takes_the_kernels_time_and_the_cost_files_work():
    read = spec.load_reader("mla_decode_attn_roofline_share").read
    trace = {"kernels": {"mla_paged_decode_attention_chunked":
                         {"seconds": 0.02, "calls": 1800}}, "programs": {}}
    ctx = ctx_of(trace=trace, engine_records=records())
    got = read(ctx)
    # 100 steps a request inside the span, at a context of 850 + 301 * 0.5
    f = published()
    _fl, by = mla_cost.absorbed_decode(f, [850 + 301 * (105 - 90) / 30])
    least = 2 * 100 * by * f["num_hidden_layers"] / 819e9
    assert got == pytest.approx(100 * least / 0.02)
    assert 0 < got < 100
    assert ctx["notes"]["mla_decode_attn_roofline_share"]["bound"] == "memory"
    # a trace without the kernel, a configuration without latent pages
    assert read(ctx_of(trace={"kernels": {}, "programs": {}})) is None
    assert read(ctx_of(trace=None)) is None
    other = ctx_of(trace=trace, engine_records=records())
    other["config"] = {"num_hidden_layers": 4}
    assert read(other) is None


def test_the_mla_prefill_reader_counts_the_shared_pages_as_cached():
    read = spec.load_reader("mla_prefill_attn_roofline_share").read
    trace = {"kernels": {"mla_ragged_prefill_attention":
                         {"seconds": 0.004, "calls": 18}}, "programs": {}}
    first, second = list(range(3, 103)), list(range(3, 103)) + [7] * 60
    rows = [{"id": "m1", "due": 99.0, "sender": "u"},
            {"id": "m2", "due": 104.0, "sender": "u"}]
    recs = {"m1": {"prompt": first, "first_t": 99.5},
            "m2": {"prompt": second, "first_t": 104.5}}
    ctx = ctx_of(trace=trace, rows=rows, engine_records=recs,
                 trace_counters={"latent_prefix_tokens_reused": 96})
    got = read(ctx)
    f = published()
    # m1 fell before the span; m2: 96 tokens (six pages) shared, 64 new
    flops, moved = mla_cost.absorbed_prefill(f, [(96, 64)])
    least = max(flops / 197e12, moved / 819e9) * f["num_hidden_layers"]
    assert got == pytest.approx(100 * least / 0.004)
    note = ctx["notes"]["mla_prefill_attn_roofline_share"]
    assert note["prefix_tokens_by_records"] == 96 == note[
        "prefix_tokens_by_program"]
    assert read(ctx_of(trace={"kernels": {}, "programs": {}})) is None


def test_the_mla_decode_step_reader_counts_what_a_step_must_move():
    read = spec.load_reader("mla_decode_step_roofline_share").read
    f = published()
    w = mla_cost.weights(f)
    steps = 1000
    counters = {"moe_expert_step_slots": steps * w["routed_layers"] * 20,
                "moe_expert_hits": steps * w["routed_layers"] * 5,
                "moe_assignments": 80000, "moe_held_assignments": 10000}
    trace = {"kernels": {}, "programs": {
        "_decode_resident_greedy": {"busy_s": 9.0, "span_s": 9.5,
                                    "calls": 100},
        "_prefill_ragged_insert": {"busy_s": 2.0, "span_s": 2.0,
                                   "calls": 50}}}
    ctx = ctx_of(trace=trace, trace_counters=counters,
                 engine_records=records())
    got = read(ctx)
    note = ctx["notes"]["mla_decode_step_roofline_share"]
    assert note["steps"] == steps and note["decode_s"] == 9.0
    assert note["held_experts_hit_a_step_a_layer"] == 5
    assert note["expert_share"] == 0.25
    expected = steps * (w["fixed"] + w["routed_layers"] * 5 * w["expert"])
    assert expected < note["bytes"] < expected * 1.01   # + latent rows
    assert got == pytest.approx(100 * note["least_s"] / 9.0)
    assert 0 < got < 100
    assert read(ctx_of(trace=trace)) is None              # no counters
    assert read(ctx_of(trace=None, trace_counters=counters)) is None


# ---------------------------------------------------------------- whole runs


@pytest.fixture(scope="module")
def xla_cache(tmp_path_factory):
    return tmp_path_factory.mktemp("xla")


def whole_run(tmp_path, xla_cache, seed, trace=0):
    """``run.py`` of ``tiny-dsv2.chat`` in a process of its own (what a
    broken sampler reads under a following check is held by
    ``test_bench_lfm2.py``'s run: the check is the same)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", TMPDIR=str(tmp_path),
               JAX_COMPILATION_CACHE_DIR=str(xla_cache))
    env.pop("XLA_FLAGS", None)
    run_py = str(ROOT / "benchmark" / "run.py")
    argv = [run_py, "--spec", str(SPEC), "--workload", "tiny-dsv2.chat",
            "--platform", "cpu", "--seed", str(seed), "--seconds", "3",
            "--trace", str(trace)]
    proc = subprocess.run([sys.executable, *argv], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    return json.loads(lines[-1]), json.loads(lines[-2]), proc.stderr


def test_a_whole_tiny_dsv2_run_ends_in_the_contract_line(tmp_path, xla_cache):
    """Traced, so that the readers run: on the CPU there is no device
    trace and the three roofline readers return nothing and raise
    nothing; the counter readers read."""
    out, facts, err = whole_run(tmp_path, xla_cache, 2 ** 31 + 11, trace=1)
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert facts["reference"] == "benchmark/reference/deepseek_v2_decoder.py"
    assert facts["routing_followed"] is True
    assert facts["logit_gaps"] and max(facts["logit_gaps"]) <= facts[
        "logit_tol"]
    assert "correct True" in err.strip().splitlines()[-1]
    m = out["metrics"]
    assert m["moe_dropped_share"]["value"] == 0.0
    assert 5.0 < m["moe_held_choice_share"]["value"] < 60.0   # 25 is even
    assert m["prefix_hit_share"]["value"] > 0
    for silent in ("mla_decode_attn_roofline_share",
                   "mla_prefill_attn_roofline_share",
                   "mla_decode_step_roofline_share"):
        assert silent not in m
    c = facts["counters_window"]
    assert 0 < c["moe_held_assignments"] < c["moe_assignments"]
    assert c["moe_dropped_assignments"] == 0
    assert 0 < c["moe_expert_hits"] <= c["moe_expert_step_slots"]
    assert c["latent_prefix_tokens_reused"] == c["prefix_reused_tokens"] > 0

