"""One whole ``run.py`` at a tiny size on the CPU: it must end in the one
JSON line the contract fixes. And the refusals: no accelerator, no
result."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SPEC = ROOT / "tests" / "benchmark" / "tiny" / "spec.json"


def run(tmp_path, *args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"),
               TMPDIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), *args],
        cwd=tmp_path, env=env, capture_output=True, text=True,
        timeout=timeout)


def last_line(proc):
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_tiny_cell_ends_in_the_contract_line(tmp_path, trace):
    out = last_line(run(tmp_path, "--spec", str(SPEC), "--workload",
                        "tiny.digest" if trace else "tiny.chat",
                        "--platform", "cpu", "--seed", str(2 ** 31 + 11),
                        "--seconds", "3", "--trace", str(trace)))
    assert set(out) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] > 0
    assert out["device"]["platform"] == "cpu" and "rehearsal" in out
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        out["device"])
    names = set(out["metrics"])
    if trace:
        assert {"loadgen_late_ms_p90", "broker_publish_ms_p50",
                "service_overhead_ms_p50", "queue_wait_ms_p90",
                "batch_occupancy", "prefix_hit_share"} <= names
        # nothing read from a device without one
        assert not names & {"prefill_ms_per_ktok", "decode_ms_per_step",
                            "reply_p90_ms"}
        assert "busy_s" not in out["device"]
    else:
        assert names == {"reply_p90_ms", "ttft_p90_ms", "tpot_p90_ms",
                         "out_tokens_per_s", "setup_s"}
    for m in out["metrics"].values():
        # an end-to-end metric is never 0; a layer's may be (no prefix
        # is shared in the single-turn mix)
        assert isinstance(m["value"], float)
        assert m["value"] >= 0 if trace else m["value"] > 0
        assert m["unit"]


def test_no_tpu_no_result(tmp_path):
    proc = run(tmp_path, "--spec", str(SPEC), "--workload", "tiny.chat",
               "--seed", "1", "--seconds", "1", "--trace", "0")
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_fewer_chips_than_the_cell_asks_for(tmp_path):
    # --platform cpu gives a four-chip cell four virtual devices; force one
    env_flags = "--xla_force_host_platform_device_count=1"
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=env_flags,
               TMPDIR=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--spec",
         str(SPEC), "--workload", "tiny-x4.chat", "--platform", "cpu",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
