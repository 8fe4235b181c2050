"""``BENCHMARK.json`` and the files it names hang together: every cell's
configuration and traffic load, every metric has its reader, and the
harness holds no list of names."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import spec  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_loads_from_data(cell):
    c = spec.load_cell(str(ROOT / "BENCHMARK.json"), cell)
    cfg = spec.model_config(c.config)
    assert cfg.head_dim == c.config["head_dim"]
    assert c.config["source"] == c.config_entry["source"]
    gen = spec.load_generator(c.traffic["generator"])
    plan = gen.plan(c.traffic, 1, BENCH["run_seconds"])
    longest = max(len(a["text"]) + a["max_new_tokens"]
                  for a in plan["arrivals"])
    assert longest + 64 < c.config["serving"]["max_seq"]
    assert c.traffic["rate_per_s"] == pytest.approx(
        0.8 * c.traffic["knee_per_s"], rel=0.02)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_a_reader(metric):
    assert callable(spec.load_reader(metric).read)


def test_moves_name_end_to_end_metrics():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert all(m["moves"] in e2e for m in BENCH["per_layer"])
    assert all(m["bound"] <= 0.1 for m in BENCH["end_to_end"])


def test_traffic_may_inherit(tmp_path):
    (tmp_path / "a.json").write_text(json.dumps(
        {"generator": "sessions", "rate_per_s": 2.0, "warm_s": 1}))
    (tmp_path / "b.json").write_text(json.dumps(
        {"base": "a", "rate_per_s": 3.5}))
    got = spec.load_traffic("b", str(tmp_path))
    assert got["rate_per_s"] == 3.5 and got["warm_s"] == 1
    assert got["generator"] == "sessions" and got["name"] == "b"


def test_unknown_workload_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.load_cell(str(ROOT / "BENCHMARK.json"), "nope.nope")


def test_a_new_cell_arrives_as_entries_and_data(tmp_path):
    """A later PR's cell is a configuration file, a mix file and two
    entries: a spec that adds them loads the cell with no new code."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cfg = json.loads((ROOT / bench["configs"][0]["file"]).read_text())
    cfg.update(name="other-9b", num_key_value_heads=4,
               num_hidden_layers=24)
    cfg_file = tmp_path / "other-9b.json"
    cfg_file.write_text(json.dumps(cfg))
    (tmp_path / "once.json").write_text(json.dumps(
        {"base": "chat", "turns": {"dist": "fixed", "value": 1}}))
    bench["configs"].append({
        "name": "other-9b", "file": str(cfg_file), "source": cfg["source"],
        "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({"name": "other9b.once", "config": "other-9b",
                               "traffic": "once", "chips": 1,
                               "why": "test"})
    (tmp_path / "chat.json").write_text(
        (ROOT / "benchmark" / "traffic" / "chat.json").read_text())
    bench["traffic_dir"] = str(tmp_path)
    cell = spec.Cell(bench, "other9b.once")
    model = spec.model_config(cell.config)
    assert (model.n_heads, model.n_kv_heads, model.n_layers) == (32, 4, 24)
    assert cell.traffic["generator"] == "sessions"
    assert cell.traffic["turns"]["value"] == 1
