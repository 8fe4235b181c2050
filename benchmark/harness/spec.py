"""What a run is made of, read from data: ``BENCHMARK.json`` names the
cell, the cell names a configuration and a traffic mix, and each of those
is a file under ``benchmark/`` found by that name. Nothing here lists a
name: a later PR adds files and entries, and edits none that is there."""

from __future__ import annotations

import dataclasses
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
    """Import one file of the benchmark by path (a generator, a per-layer
    reader or a configuration's reference), without a registry."""
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
        # no default: every configuration says what it is held to
        ref = self.config.get("reference")
        if not ref or not os.path.isfile(os.path.join(ROOT, ref)):
            raise SpecError(
                f"configuration {self.config_entry['name']!r} "
                f"({self.config_entry['file']}): \"reference\" is {ref!r}, "
                "not a file of the repo")
        # only a test's own spec names another directory of mixes
        tdir = bench.get("traffic_dir")
        self.traffic = load_traffic(self.entry["traffic"],
                                    tdir and os.path.join(ROOT, tdir))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    def reference(self):
        """The configuration's plain reference, a module with ``Q_BLOCK``,
        ``dims(cfg_file)`` and ``logits_at(params, dims, tokens, at)``
        (``benchmark/README.md``). The reference of a configuration that
        routes declares ``FOLLOWS_ROUTING = True``, and its ``logits_at``
        takes a fifth argument, ``routing=None``: the program's reported
        choices, ``[T, L_routed, k]`` int16 (``e``, or ``~e`` for a
        choice the program left out), which ``check.logit_gaps`` hands it
        and which it computes in place of its own top-k (the README has
        the whole contract). It imports jax, so it is loaded when the
        check needs it and not with the cell."""
        return load_module(os.path.join(ROOT, self.config["reference"]),
                           "reference_" + self.config_entry["name"])


def load_cell(spec_path: str, workload: str) -> Cell:
    return Cell(_load_json(spec_path or DEFAULT_SPEC), workload)


# a common field of the program's ``ModelConfig`` <- the key a published
# ``config.json`` most often gives it under
PUBLISHED = {
    "name": "name", "vocab_size": "vocab_size", "dim": "hidden_size",
    "n_layers": "num_hidden_layers", "n_heads": "num_attention_heads",
    "n_kv_heads": "num_key_value_heads", "ffn_dim": "intermediate_size",
    "norm_eps": "rms_norm_eps", "rope_theta": "rope_theta",
    "max_seq_len": "max_position_embeddings",
    "tie_embeddings": "tie_word_embeddings",
    "sliding_window": "sliding_window"}
# what a published file says by leaving the key out
ABSENT = {"tie_embeddings": False, "sliding_window": None}


def model_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` from a configuration file: each
    common field from its ``PUBLISHED`` key where the file has that key,
    and over them the file's optional ``program`` group, names of
    ``ModelConfig`` fields with their values, for what an architecture
    has beyond the common ones and for a common field that its published
    file names otherwise (``{"norm_eps": 1e-05}`` under a file that has
    ``norm_eps``). A common field that neither supplies is an error, never
    the program's default."""
    from swarmdb_tpu.models.configs import ModelConfig

    program = cfg.get("program", {})
    known = {f.name for f in dataclasses.fields(ModelConfig)}
    unknown = sorted(set(program) - known)
    if unknown:
        raise SpecError(f"{cfg['name']}: \"program\" names {unknown}, "
                        "which the program's ModelConfig does not have")
    fields = {**ABSENT,
              **{f: cfg[key] for f, key in PUBLISHED.items() if key in cfg},
              **program}
    missing = [f for f in PUBLISHED if f not in fields]
    if missing:
        raise SpecError(
            f"{cfg.get('name')}: nothing gives the program's {missing}: "
            f"neither {[PUBLISHED[f] for f in missing]} among the file's "
            "keys nor its \"program\" group")
    fields["rope_theta"] = float(fields["rope_theta"])
    fields["tie_embeddings"] = bool(fields["tie_embeddings"])
    built = ModelConfig(**fields)
    if "head_dim" in cfg and built.head_dim != cfg["head_dim"]:
        raise SpecError(f"{cfg['name']}: head_dim {cfg['head_dim']} in the "
                        f"file, {built.head_dim} in the program's "
                        "configuration built from it")
    return built
