"""Bench harness contract tests: one parsed JSON line per mode and a
compact final summary when a run succeeds; a non-zero exit and no metric
line when a backend mode finds no TPU or fails."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402


def test_chip_peak_flops_mapping():
    assert bench.chip_peak_flops("TPU v5e") == 197e12
    assert bench.chip_peak_flops("TPU v5p") == 459e12
    assert bench.chip_peak_flops("TPU v4") == 275e12
    assert bench.chip_peak_flops("cpu") is None
    assert bench.chip_peak_flops("") is None


def test_active_params_dense_vs_moe():
    from swarmdb_tpu.models.configs import TINY_DEBUG, TINY_MOE

    assert bench.active_params(1000, TINY_DEBUG) == 1000
    total = 287552  # measured param count of tiny-moe
    act = bench.active_params(total, TINY_MOE)
    expert_ffn = 3 * TINY_MOE.dim * TINY_MOE.ffn_dim
    expected = total - TINY_MOE.n_layers * expert_ffn * (
        TINY_MOE.n_experts - TINY_MOE.experts_per_token
    )
    assert act == expected
    assert 0 < act < total


def _run_bench(**env):
    return subprocess.run(
        [sys.executable, "bench.py"], capture_output=True, text=True,
        timeout=180, env=dict(os.environ, **env),
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )


def _metric_lines(stdout):
    out = []
    for line in stdout.strip().splitlines():
        try:
            parsed = json.loads(line)
        except json.JSONDecodeError:
            continue
        if isinstance(parsed, dict) and "metric" in parsed:
            out.append(parsed)
    return out


def test_no_tpu_under_auto_fails_without_a_metric_line():
    """A backend mode under SWARMDB_BENCH_PLATFORM=auto that finds no TPU
    (this suite pins jax to the CPU) exits non-zero and prints no metric
    line: nothing falls back to the CPU unasked."""
    out = _run_bench(SWARMDB_BENCH_MODE="serve",
                     SWARMDB_BENCH_PLATFORM="auto",
                     SWARMDB_BENCH_MODEL="tiny-debug",
                     SWARMDB_BENCH_SECONDS="1", JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert _metric_lines(out.stdout) == []
    assert "no TPU" in out.stderr


def test_echo_mode_runs():
    result = bench.bench_echo(seconds=0.5)
    assert result["metric"] == "echo_messages_per_sec"
    assert result["value"] > 0
    assert result["unit"] == "msgs/sec"


def test_unknown_mode_emits_parsed_json_line():
    env = dict(os.environ, SWARMDB_BENCH_MODE="bogus-mode")
    out = subprocess.run(
        [sys.executable, "bench.py"], capture_output=True, text=True,
        timeout=120, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert out.returncode == 0
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert "error" in line
    assert line["vs_baseline"] == 0.0


def test_failing_mode_exits_nonzero_without_a_metric_line():
    """A mode that fails exits non-zero; no echo number stands in for it."""
    out = _run_bench(SWARMDB_BENCH_MODE="serve",
                     SWARMDB_BENCH_PLATFORM="cpu",
                     SWARMDB_BENCH_MODEL="definitely-not-a-model",
                     SWARMDB_BENCH_SECONDS="1")
    assert out.returncode != 0
    assert _metric_lines(out.stdout) == []
    assert "definitely-not-a-model" in out.stderr


def test_run_all_exits_nonzero_when_a_mode_fails(monkeypatch, capsys):
    """mode=all: one failed child makes the run's exit code non-zero and
    names the mode in the final summary."""
    monkeypatch.setattr(bench, "_ALL_MODES", ("echo", "serve"))
    monkeypatch.setattr(
        bench, "_run_mode_subprocess",
        lambda m, platform, limit: (
            {"error": "mode serve: child failed (rc=1): no TPU", "rc": 1}
            if m == "serve" else bench.bench_echo(0.2)))
    assert bench._run_all() == 1
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["error"] == "failed modes: ['serve']"
    assert "err" in summary["modes"]["serve"]
    assert summary["modes"]["echo"]["v"] > 0


def _fake_detail(mode, value):
    # a plausibly maximal detailed mode result (mirrors serve's real keys)
    return {
        "metric": f"{mode}_completed_messages_per_sec", "value": value,
        "unit": "msgs/sec", "vs_baseline": round(value / 500.0, 4),
        "mode": mode, "model": "llama-1b-bench", "agents": 100,
        "tokens_per_sec": 2970.4, "prompt_tokens_per_sec": 42370.1,
        "mfu": 0.41123, "p50_send_to_first_token_s": 0.5961,
        "window_s": 20.01, "window_completed": 3712,
        "prompt_tokens_reused_per_sec": 9321.0,
        "prompt_tokens_computed_per_sec": 33049.1,
        "device": "TPU_0(process=0,(0,0,0,0))", "device_kind": "TPU v5e",
        "platform": "tpu", "params_total": 886000000,
        "params_active": 886000000, "flops_per_token": 1772000000,
        "chip_peak_flops": 197e12, "kv_cache": "paged",
        "kv_pool_pages": 6145, "kv_page_size": 16,
        "prefix_cache": {"cached_pages": 5620, "hit_tokens": 56848,
                         "miss_tokens": 87440},
        "prefix_hit_rate": 0.394,
        "p50_ttft_by_priority": {"0": 14.6, "1": 2.84, "2": 2.72, "3": 2.71},
        "openloop": {"arrival_rate_per_s": 92.8, "sent": 1392,
                     "measured": 1390, "p50_ttft_s": 0.596,
                     "p99_ttft_s": 0.903},
    }


def test_compact_summary_fits_tail_capture():
    """VERDICT r4 weak #2: the FINAL line must stay under ~1500 bytes so the
    driver's 2000-byte stdout tail always contains a parseable record —
    even with maximal per-mode detail and error strings present."""
    results = {m: _fake_detail(m, 185.6) for m in
               ("echo", "serve", "group", "tooluse", "swarm100")}
    results["echo"]["native_broker_msgs_per_sec"] = 2658.2
    results["tooluse"] = {"error": "x" * 2000}  # worst-case error string
    line = bench._compact_summary(results)
    raw = json.dumps(line)
    assert len(raw) < 1500, f"summary line is {len(raw)} bytes"
    parsed = json.loads(raw)
    # headline contract comes from serve
    assert parsed["metric"] == "serve_completed_messages_per_sec"
    assert parsed["value"] == 185.6
    assert parsed["unit"] == "msgs/sec"
    assert parsed["mode"] == "all"
    # every mode appears with at least a value or error marker
    for m in ("echo", "serve", "group", "swarm100"):
        assert parsed["modes"][m]["v"] == 185.6
    assert "err" in parsed["modes"]["tooluse"]
    # scalar extras survive
    assert parsed["modes"]["serve"]["mfu"] == 0.41123
    assert parsed["modes"]["serve"]["pl"] == "tpu"
    assert parsed["modes"]["echo"]["native"] == 2658.2


def test_unknown_tpu_kind_is_an_error_not_the_cpu_row():
    """A bench record's kernel-profile block on a TPU whose device_kind
    the peaks table does not know raises; it used to print an "mfu"
    against the CPU row."""
    from swarmdb_tpu.obs.profiler import KernelProfiler

    prof = KernelProfiler(enabled=True)
    prof.set_platform("tpu", "TPU v9 imaginary")
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        prof.kernel_profile()
    prof.set_platform("tpu", "TPU v5 lite")
    assert prof.kernel_profile()["platform"] == "tpu"


def test_compact_summary_all_modes_errored():
    line = bench._compact_summary(
        {m: {"error": "boom"} for m in ("echo", "serve")}, error="watchdog")
    raw = json.dumps(line)
    assert len(raw) < 1500
    assert line["metric"] == "all_error"
    assert line["value"] == 0.0
    assert line["error"] == "watchdog"


def test_run_all_emits_detail_lines_then_compact_summary(monkeypatch, capsys):
    """The orchestrator prints one detail line per mode, final line compact."""
    monkeypatch.setattr(bench, "_ALL_MODES", ("echo",))
    monkeypatch.setenv("SWARMDB_BENCH_SECONDS", "0.5")
    assert bench._run_all() == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    assert len(lines) == 2
    detail, summary = lines
    assert detail["mode"] == "echo"
    assert detail["value"] > 0
    assert summary["mode"] == "all"
    assert summary["modes"]["echo"]["v"] == detail["value"]
    assert len(json.dumps(summary)) < 1500


def test_longctx_promoted_into_all():
    """VERDICT r5 #5: S=1024 must finally appear in the driver record —
    longctx runs in mode=all (last, so budget squeezes shed it before
    the headline modes) and probes the backend like any LLM mode."""
    assert "longctx" in bench._ALL_MODES
    assert bench._ALL_MODES[-1] == "longctx"
    assert "longctx" in bench._NEEDS_BACKEND


def test_dpserve_registered_in_all():
    """dpserve (the DP-scaling A/B) runs in mode=all but never probes the
    TPU — it is a virtual-CPU-device measurement by design."""
    assert "dpserve" in bench._MODES
    assert "dpserve" in bench._ALL_MODES
    assert "dpserve" not in bench._NEEDS_BACKEND
    # its scaling ratio surfaces in the compact summary
    assert ("dpx", "dp_scaling_x") in bench._SUMMARY_KEYS


def test_serve_mode_end_to_end_cpu(monkeypatch):
    """The full serve-mode harness (prewarm -> closed window -> open-loop
    latency window) over the tiny model on CPU: contract fields present,
    openloop TTFT measured from fresh samples."""
    monkeypatch.setenv("SWARMDB_BENCH_MODEL", "tiny-debug")
    monkeypatch.setenv("SWARMDB_BENCH_BATCH", "8")
    monkeypatch.setenv("SWARMDB_BENCH_SEQ", "128")
    monkeypatch.setenv("SWARMDB_BENCH_WARM_COMPLETIONS", "2")
    monkeypatch.setenv("SWARMDB_BENCH_AGENTS", "8")
    import tempfile

    with tempfile.TemporaryDirectory() as logs:
        monkeypatch.setenv("SWARMDB_BENCH_LOGS_DIR", logs)
        result = bench.bench_serve(seconds=3.0)
        # observability artifacts deposited with the run (ISSUE 2)
        assert result["trace_artifact"].startswith(logs)
        assert result["flight_artifact"].startswith(logs)
        trace = json.load(open(result["trace_artifact"]))
        assert any(e.get("name") == "engine.decode_chunk"
                   for e in trace["traceEvents"])
        flight = json.load(open(result["flight_artifact"]))
        assert flight["steps"] and flight["requests"]
    assert result.get("phase_shares"), result.get("phase_seconds")
    assert abs(sum(result["phase_shares"].values()) - 1.0) < 0.01
    assert result["metric"] == "completed_messages_per_sec"
    assert result["value"] > 0
    assert result["prompt_tokens_per_sec"] > 0
    assert result["kv_cache"] == "dense"
    ol = result.get("openloop")
    assert ol is not None and ol["p50_ttft_s"] > 0
    # open-loop latency must not be queue-depth-dominated: with this tiny
    # 3 s window the closed loop is barely saturated, so assert the same
    # order of magnitude rather than strict ordering (which is marginal
    # and flaky here; the real bench windows are 20 s+)
    assert ol["p50_ttft_s"] <= result["p50_send_to_first_token_s"] * 2 + 0.1


def test_tooluse_mode_record_contract(monkeypatch):
    """The tooluse bench line's record contract (ISSUE r6 satellite): the
    phase family explains where the time went, the prefix hit/miss token
    counts are present, and every reply to a function_call is a
    function_result."""
    monkeypatch.setenv("SWARMDB_BENCH_MODEL", "tiny-moe")
    monkeypatch.setenv("SWARMDB_BENCH_BATCH", "8")
    monkeypatch.setenv("SWARMDB_BENCH_SEQ", "128")
    monkeypatch.setenv("SWARMDB_BENCH_WARM_COMPLETIONS", "2")
    monkeypatch.setenv("SWARMDB_BENCH_AGENTS", "8")
    monkeypatch.setenv("SWARMDB_BENCH_OPENLOOP", "0")
    import tempfile

    with tempfile.TemporaryDirectory() as logs:
        monkeypatch.setenv("SWARMDB_BENCH_LOGS_DIR", logs)
        result = bench.bench_tooluse(seconds=3.0)
    assert result["metric"] == "tooluse_completed_messages_per_sec"
    assert result["value"] > 0
    # per-phase breakdown present and complete
    assert set(result["phase_seconds"]) == set(bench._PHASES)
    assert abs(sum(result["phase_shares"].values()) - 1.0) < 0.01
    # prefix-cache evidence rides the record
    pc = result["prefix_cache"]
    assert {"hit_tokens", "miss_tokens", "cached_pages"} <= set(pc)
    # function_call -> function_result reply check
    assert result["function_results_emitted"] > 0
    assert result["function_results_emitted"] >= result["window_completed"]
