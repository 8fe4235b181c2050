"""The ``lanes`` layout on four virtual CPU devices: a four-chip cell
arrives as a configuration file and an entry, with no new code."""

import json
import os
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
SPEC = ROOT / "tests" / "benchmark" / "tiny" / "spec.json"


def test_four_lane_cell_runs_from_data(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"),
               TMPDIR=str(tmp_path))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(ROOT / "benchmark" / "run.py"), "--spec",
         str(SPEC), "--workload", "tiny-x4.chat", "--platform", "cpu",
         "--seed", "5", "--seconds", "3", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    out, facts = json.loads(lines[-1]), json.loads(lines[-2])
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["count"] == 4
    # four lanes of the configuration's max_batch each
    assert facts["max_batch"] == 16
