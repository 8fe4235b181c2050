"""What lets a second architecture arrive as files and entries (PR 27):
the program's configuration is built from the configuration file with its
``program`` group laid over the common fields, and every configuration
names the reference it is held to, whose dimensions come from the file."""

import dataclasses
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import spec  # noqa: E402

MISTRAL = ROOT / "benchmark" / "configs" / "mistral-7b-v0.3.json"
TINY = ROOT / "tests" / "benchmark" / "tiny"
MIXED = ROOT / "tests" / "benchmark" / "fixtures" / "mixed-layers.json"


def mistral():
    return json.loads(MISTRAL.read_text())


def test_the_mistral_file_builds_the_configuration_it_always_built():
    # written out, not computed by the function under test: what PR 24's
    # model_config() returned for this file, field by field
    assert dataclasses.asdict(spec.model_config(mistral())) == {
        "name": "mistral-7b-v0.3", "vocab_size": 32768, "dim": 4096,
        "n_layers": 16, "n_heads": 32, "n_kv_heads": 8, "ffn_dim": 14336,
        "norm_eps": 1e-05, "rope_theta": 1000000.0, "max_seq_len": 32768,
        "tie_embeddings": False, "n_experts": 0, "experts_per_token": 2,
        "sliding_window": None}


@pytest.mark.parametrize("name, experts", [
    ("tiny", 0), ("tiny-x4", 0), ("tiny-moe", 4)])
def test_the_tests_tiny_files_build_what_they_always_built(name, experts):
    # what PR 27's model_config() returned for these files
    assert dataclasses.asdict(spec.model_config(json.loads(
        (TINY / f"{name}.json").read_text()))) == {
        "name": name, "vocab_size": 512, "dim": 64, "n_layers": 2,
        "n_heads": 4, "n_kv_heads": 2, "ffn_dim": 128, "norm_eps": 1e-05,
        "rope_theta": 10000.0, "max_seq_len": 256, "tie_embeddings": False,
        "n_experts": experts, "experts_per_token": 2,
        "sliding_window": None}


def test_a_published_file_loads_as_it_is_named():
    """No ``rms_norm_eps`` (the file calls it ``norm_eps``, and says so to
    the program in its ``program`` group), no ``head_dim``, no
    ``tie_word_embeddings``, and layers of more than one kind."""
    cfg = json.loads(MIXED.read_text())
    assert not {"rms_norm_eps", "head_dim", "tie_word_embeddings"} & set(cfg)
    built = spec.model_config(cfg)
    assert (built.norm_eps, built.n_layers, built.head_dim) == (1e-05, 8, 16)
    assert built.tie_embeddings is False and built.sliding_window is None


@pytest.mark.parametrize("field, key", [
    ("norm_eps", "rms_norm_eps"), ("rope_theta", "rope_theta"),
    ("max_seq_len", "max_position_embeddings"), ("dim", "hidden_size")])
def test_a_field_that_nothing_supplies_is_an_error_that_names_it(field, key):
    """Never the program's default: ``ModelConfig`` has one for the first
    three, and a file that left the key out would run with it unseen."""
    cfg = mistral()
    del cfg[key]
    with pytest.raises(spec.SpecError,
                       match=f"mistral-7b-v0.3.*{field}.*{key}"):
        spec.model_config(cfg)
    cfg["program"] = {field: 4096 if field == "dim" else 0.5}
    assert getattr(spec.model_config(cfg), field) in (4096, 0.5)


def test_the_dense_reference_reads_its_dimensions_from_the_file():
    from benchmark.reference import decoder

    # the four that check.py took from the program's configuration
    # before PR 27
    assert decoder.dims(mistral()) == {
        "n_heads": 32, "n_kv_heads": 8, "eps": 1e-05, "theta": 1000000.0}


def test_the_program_group_lays_over_the_common_fields():
    cfg = mistral()
    cfg["program"] = {"n_experts": 8, "experts_per_token": 3,
                      "sliding_window": 4096}
    built = spec.model_config(cfg)
    assert (built.n_experts, built.experts_per_token,
            built.sliding_window) == (8, 3, 4096)
    assert built.is_moe and built.dim == 4096


def test_an_unknown_name_in_program_is_an_error_that_names_it():
    cfg = mistral()
    cfg["program"] = {"n_experts": 8, "n_shared_experts": 2}
    with pytest.raises(spec.SpecError, match="n_shared_experts"):
        spec.model_config(cfg)


def test_a_head_size_the_program_would_not_run_is_an_error():
    cfg = mistral()
    cfg["head_dim"] = 64        # the program would still run 4096 / 32
    with pytest.raises(spec.SpecError, match="head_dim 64.*128"):
        spec.model_config(cfg)


def bench_with(tmp_path, cfg):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    path = tmp_path / "other.json"
    path.write_text(json.dumps(cfg))
    bench["configs"][0]["file"] = str(path)
    return bench


def test_a_configuration_without_reference_is_an_error_that_names_it(
        tmp_path):
    cfg = mistral()
    del cfg["reference"]
    with pytest.raises(spec.SpecError, match="mistral-7b-v0.3.*reference"):
        spec.Cell(bench_with(tmp_path, cfg), "mistral7b.chat")


def test_a_reference_that_is_no_file_is_an_error_that_names_it(tmp_path):
    cfg = mistral()
    cfg["reference"] = "benchmark/reference/nowhere.py"
    with pytest.raises(spec.SpecError, match="nowhere.py"):
        spec.Cell(bench_with(tmp_path, cfg), "mistral7b.chat")


def test_the_cell_loads_the_reference_its_configuration_names():
    cell = spec.load_cell(str(ROOT / "BENCHMARK.json"), "mistral7b.chat")
    ref = cell.reference()
    assert Path(ref.__file__) == ROOT / "benchmark/reference/decoder.py"
    assert ref.Q_BLOCK > 0 and callable(ref.logits_at)
    assert ref.dims(cell.config)["n_kv_heads"] == 8


def test_the_rehearsals_zero_draw_reaches_a_family_that_routes(monkeypatch):
    """``aot_rehearsal.py`` plans memory without drawing weights: it
    replaces ``llama.random_dense``, which the routed family's
    ``init_params`` looks up when it is called (since PR 31), so a
    sparse-expert rehearsal draws no weight either."""
    import jax
    import jax.numpy as jnp

    from swarmdb_tpu.models import llama, mixtral

    monkeypatch.setattr(llama, "random_dense",
                        lambda key, shape, fan_in, dtype: jnp.zeros(shape,
                                                                    dtype))
    cfg = spec.model_config(json.loads((TINY / "tiny-moe.json").read_text()))
    params = mixtral.init_params(cfg, jax.random.PRNGKey(0))
    drawn = {"embed": params["embed"], **{
        n: a for n, a in params["layers"].items() if not n.endswith("norm")}}
    assert {"router", "w_gate", "w_up", "w_down"} <= set(drawn)
    assert not any(bool(a.any()) for a in drawn.values())
