"""The reader of the wave scan's time a thousand prompt tokens (PR 53) on
a cut trace of two waves, three Mamba-2 layers each: what it reads and
notes, and ``None`` where the trace holds no such kernel (the parent
commit, whose waves are scanned by XLA's loop), no trace or no token."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import spec, trace_reduce  # noqa: E402

NAME = "ssm_wave_scan_ms_per_ktok"
COUNTERS = {"prefill_packed_tokens": 500, "ssm_wave_segments": 7,
            "ssm_wave_segment_tokens": 500}


def reduced():
    with open(ROOT / "tests" / "benchmark" / "fixtures"
              / "trace_wave_scan.json") as f:
        return trace_reduce.reduce(json.load(f))


def ctx_of(**more):
    with open(ROOT / "benchmark" / "configs"
              / "nemotron-3-nano-30b-a3b.json") as f:
        config = json.load(f)
    ctx = {"config": config, "model": spec.model_config(config),
           "notes": {}, "page_size": 16, "decode_chunk": 8,
           "device_kind": "TPU v5 lite", "trace_span": (100.0, 110.0),
           "trace_counters": {}, "counters": {}, "rows": [],
           "engine_records": {}, "trace": None}
    ctx.update(more)
    return ctx


def test_the_wave_scan_reader_reads_the_kernels_time_a_thousand_tokens():
    """0.2 ms a call in the first wave and 0.3 in the second: 1.5 ms over
    500 prompt tokens."""
    read = spec.load_reader(NAME).read
    tr = reduced()
    assert tr["kernels"]["ssm_wave_scan"] == {
        "seconds": pytest.approx(0.0015), "calls": 6}
    ctx = ctx_of(trace=tr, trace_counters=COUNTERS)
    assert read(ctx) == pytest.approx(3.0)
    assert ctx["notes"][NAME] == {
        "seconds": pytest.approx(0.0015), "calls": 6, "tokens": 500,
        "segments_a_layer": 7, "segment_tokens": 500}
    # the scan's part of the prefill programs' time
    assert spec.load_reader("prefill_ms_per_ktok").read(
        ctx) == pytest.approx(1e3 * 0.0085 / 0.5)


@pytest.mark.parametrize("missing", ["kernel", "trace", "tokens"])
def test_the_wave_scan_reader_reads_nothing_without(missing):
    tr = reduced()
    ctx = {"kernel": ctx_of(trace=dict(tr, kernels={
               k: v for k, v in tr["kernels"].items()
               if k != "ssm_wave_scan"}), trace_counters=COUNTERS),
           "trace": ctx_of(trace=None, trace_counters=COUNTERS),
           "tokens": ctx_of(trace=tr)}[missing]
    assert spec.load_reader(NAME).read(ctx) is None and not ctx["notes"]


def test_the_wave_scan_reader_has_its_entry_for_the_one_cell():
    with open(ROOT / "BENCHMARK.json") as f:
        entry = [m for m in json.load(f)["per_layer"] if m["name"] == NAME]
    assert entry == [{
        "name": NAME, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "kernels",
        "moves": "ttft_p90_ms", "workloads": ["nemotron3-nano.chat"]}]
