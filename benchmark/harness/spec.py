"""What a run is made of, read from data: ``BENCHMARK.json`` names the
cell, the cell names a configuration and a traffic mix, and each of those
is a file under ``benchmark/`` found by that name. Nothing here lists a
name: a later PR adds files and entries, and edits none that is there."""

from __future__ import annotations

import importlib.util
import json
import os
from typing import Any, Dict

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
DEFAULT_SPEC = os.path.join(ROOT, "BENCHMARK.json")


class SpecError(Exception):
    pass


def _load_json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    """Import one file of the benchmark by path (a generator or a
    per-layer reader), without a registry."""
    if not os.path.isfile(path):
        raise SpecError(f"{name}: no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(
        "benchmark_dyn_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_traffic(name: str, directory: str = None) -> Dict[str, Any]:
    """``traffic/<name>.json``. A mix may name a ``base`` mix and hold only
    what differs from it (a rate found for another configuration)."""
    directory = directory or os.path.join(BENCH_DIR, "traffic")
    params = _load_json(os.path.join(directory, f"{name}.json"))
    base = params.pop("base", None)
    if base is not None:
        merged = load_traffic(base, directory)
        merged.update(params)
        params = merged
    params["name"] = name
    return params


def load_generator(kind: str):
    return load_module(os.path.join(BENCH_DIR, "traffic", f"gen_{kind}.py"),
                       f"gen_{kind}")


def load_reader(metric: str):
    return load_module(
        os.path.join(BENCH_DIR, "layer_metrics", f"{metric}.py"), metric)


class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""

    def __init__(self, bench: Dict[str, Any], name: str) -> None:
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SpecError(f"no workload {name!r}; known: {sorted(cells)}")
        self.name = name
        self.run_seconds = bench["run_seconds"]
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = _load_json(os.path.join(ROOT,
                                              self.config_entry["file"]))
        # only a test's own spec names another directory of mixes
        tdir = bench.get("traffic_dir")
        self.traffic = load_traffic(self.entry["traffic"],
                                    tdir and os.path.join(ROOT, tdir))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]


def load_cell(spec_path: str, workload: str) -> Cell:
    return Cell(_load_json(spec_path or DEFAULT_SPEC), workload)


def model_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` from a configuration file whose keys
    are those of the model's published ``config.json``."""
    from swarmdb_tpu.models.configs import ModelConfig

    if cfg["hidden_size"] != cfg["num_attention_heads"] * cfg["head_dim"]:
        raise SpecError("head_dim * heads != hidden_size: the program's "
                        "Llama stack derives head_dim from them")
    return ModelConfig(
        name=cfg["name"], vocab_size=cfg["vocab_size"],
        dim=cfg["hidden_size"], n_layers=cfg["num_hidden_layers"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        ffn_dim=cfg["intermediate_size"], norm_eps=cfg["rms_norm_eps"],
        rope_theta=float(cfg["rope_theta"]),
        max_seq_len=cfg["max_position_embeddings"],
        tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
        sliding_window=cfg.get("sliding_window"))
