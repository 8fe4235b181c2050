"""Operations and bytes of the attention kernels against hand counts."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import kernel_cost, peaks  # noqa: E402


def test_ragged_prefill_one_row_by_hand():
    # 4 new tokens behind a 16-token prefix, 32 query heads over 8 KV
    # heads of 128: pairs = 4 * 16 + (1 + 2 + 3 + 4) = 74
    flops, moved = kernel_cost.ragged_prefill_attention([(16, 4)], 32, 8, 128)
    assert flops == 74 * 32 * 128 * 4
    # q and out: 2 * 4 tokens * 32 heads; k and v: 2 * 20 tokens * 8 heads
    assert moved == (2 * 4 * 32 + 2 * 20 * 8) * 128 * 2


def test_ragged_prefill_rows_add():
    one = kernel_cost.ragged_prefill_attention([(0, 128)], 32, 4, 128)
    two = kernel_cost.ragged_prefill_attention([(0, 128), (0, 128)], 32, 4,
                                               128)
    assert two == (2 * one[0], 2 * one[1])


def test_paged_decode_by_hand():
    flops, moved = kernel_cost.paged_decode_attention([100, 300], 32, 8, 128)
    assert flops == 4 * 128 * 32 * 400
    assert moved == 2 * 128 * (2 * 32 * 2 + 2 * 8 * 400)


def test_peaks_table_and_unknown_device():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9")
    t, bound = peaks.least_seconds(197e12, 1.0, "TPU v5 lite")
    assert t == pytest.approx(1.0) and bound == "compute"
    t, bound = peaks.least_seconds(1.0, 819e9, "TPU v5 lite")
    assert t == pytest.approx(1.0) and bound == "memory"


MIXED = ROOT / "tests" / "benchmark" / "fixtures" / "mixed-layers.json"


def hand_made_ctx(model, config):
    """What the two roofline readers read of a traced run. One request:
    100-token prompt, 11 tokens between t=10 and t=11, all inside the
    span: 10 steps at a mean context of 100 + 5.5."""
    records = {"m1": {"prompt": [7] * 100, "first_t": 10.0, "last_t": 11.0,
                      "n_tokens": 11}}
    return {"trace": {"kernels": {
                "paged_decode_gqa_attention_chunked":
                    {"seconds": 1e-3, "calls": 20},
                "ragged_paged_prefill_attention":
                    {"seconds": 1e-3, "calls": 2}}},
            "trace_span": (9.0, 12.0), "model": model, "config": config,
            "engine_records": records, "device_kind": "TPU v5 lite",
            "rows": [{"id": "m1", "due": 9.5, "sender": "u"}],
            "page_size": 16, "notes": {}}


def test_roofline_readers_on_hand_made_records():
    from types import SimpleNamespace

    from benchmark.harness import spec

    ctx = hand_made_ctx(
        SimpleNamespace(n_heads=32, n_kv_heads=8, head_dim=128, n_layers=2),
        {"num_hidden_layers": 2})
    got = spec.load_reader("decode_attn_roofline_share").read(ctx)
    _, moved = kernel_cost.paged_decode_attention([105.5], 32, 8, 128)
    assert got == pytest.approx(100 * (10 * 2 * moved / 819e9) / 1e-3)
    assert ctx["notes"]["decode_attn_roofline_share"]["bound"] == "memory"
    got = spec.load_reader("prefill_attn_roofline_share").read(ctx)
    flops, moved = kernel_cost.ragged_prefill_attention([(0, 100)], 32, 8,
                                                        128)
    least = max(2 * flops / 197e12, 2 * moved / 819e9)
    assert got == pytest.approx(100 * least / 1e-3)
    ctx["trace"] = None
    assert spec.load_reader("decode_attn_roofline_share").read(ctx) is None


def test_layers_of_another_kind_call_no_attention_kernel():
    mixed = json.loads(MIXED.read_text())
    assert kernel_cost.attending_layers(mixed) == 2
    # a file cut in depth runs the first entries of its list
    assert kernel_cost.attending_layers(
        dict(mixed, num_hidden_layers=3)) == 0
    assert kernel_cost.attending_layers(
        dict(mixed, layer_types=["sliding_attention", "full_attention"] * 4)
    ) == 8
    del mixed["layer_types"]
    assert kernel_cost.attending_layers(mixed) == 8


@pytest.mark.parametrize("metric", ["decode_attn_roofline_share",
                                    "prefill_attn_roofline_share"])
def test_a_roofline_reader_counts_the_layers_that_attend(metric):
    """Three layers of another kind to one that attends: a quarter of the
    work of a stack of as many layers that all attend, for the same
    kernel time, whatever depth the program's configuration has."""
    from types import SimpleNamespace

    from benchmark.harness import spec

    mixed = json.loads(MIXED.read_text())
    every = {k: v for k, v in mixed.items() if k != "layer_types"}
    model = SimpleNamespace(n_heads=4, n_kv_heads=2, head_dim=16, n_layers=8)
    read = spec.load_reader(metric).read
    all_attend = read(hand_made_ctx(model, every))
    assert all_attend > 0
    assert read(hand_made_ctx(model, mixed)) == pytest.approx(all_attend / 4)
