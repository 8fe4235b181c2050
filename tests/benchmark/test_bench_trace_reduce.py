"""``trace_reduce`` on a small trace kept as a fixture (the event layout
of a v5e trace: one ``/device:TPU:0`` plane with ``XLA Ops`` and
``XLA Modules`` lines, host threads on ``/host:CPU``)."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from benchmark.harness import trace_reduce as tr  # noqa: E402

FIXTURE = Path(__file__).parent / "fixtures" / "trace_small.json"


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce(json.loads(FIXTURE.read_text()))


def test_union_and_overlap():
    cover = tr.union([(5, 7), (1, 3), (2, 4), (7, 8)])
    assert cover == [(1, 4), (5, 8)]
    assert tr.overlap(cover, 0, 10) == 6
    assert tr.overlap(cover, 3, 6) == 2


def test_busy_and_window(reduced):
    # leaves cover [1000, 5000) + [6000, 9000) + [10000, 14000) +
    # [17000, 19000); the outer while waits for the host in [9000, 10000)
    # and [14000, 16000) and is not busy then
    assert reduced["busy_s"] == pytest.approx(13000e-9)
    # the window spans every plane: the host's first event starts at 0
    assert reduced["window_s"] == pytest.approx(19000e-9)
    assert reduced["devices"] == 1


def test_per_program_sums(reduced):
    prefill = reduced["programs"]["_prefill_ragged_insert"]
    decode = reduced["programs"]["_unknown"]
    assert prefill["calls"] == 2 and decode["calls"] == 1
    assert prefill["span_s"] == pytest.approx(6000e-9)
    assert prefill["busy_s"] == pytest.approx(6000e-9)
    # the decode program waits 3000 ns for the host inside its module event
    assert decode["span_s"] == pytest.approx(10000e-9)
    assert decode["busy_s"] == pytest.approx(7000e-9)


def test_per_operation_self_time_and_kernels(reduced):
    ops = reduced["op_seconds"]
    assert ops["fusion.9"] == pytest.approx(5000e-9)
    assert ops["fusion.1"] == pytest.approx(3500e-9)
    # a container's self time is what its children leave: the outer while
    # holds two inner whiles (7000 of 10000), the inner ones are full
    assert ops["while.65"] == pytest.approx(3000e-9)
    assert ops["while.71"] == pytest.approx(0.0)
    assert reduced["breakdown"]["device_ops"][0] == [
        "fusion.9", pytest.approx(5000e-9)]
    assert all(len(name) < 64 for name, _ in
               reduced["breakdown"]["device_ops"])
    k = reduced["kernels"]
    assert k["paged_decode_gqa_attention_chunked"] == {
        "seconds": pytest.approx(2000e-9), "calls": 2}
    assert k["ragged_paged_prefill_attention"]["calls"] == 1


def test_idle_gaps_are_named_by_the_host(reduced):
    gaps = dict(reduced["breakdown"]["idle_gaps"])
    # [14000, 17000) mostly under reply_emit, [9000, 10000) under the
    # host sync, [0, 1000) under the prefill dispatch, [5000, 6000) under
    # nothing
    assert gaps["reply_emit"] == pytest.approx(3000e-9)
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(1000e-9)
    assert gaps["PjitFunction(_prefill_ragged_insert)"] == pytest.approx(
        1000e-9)
    assert gaps["host: nothing traced"] == pytest.approx(1000e-9)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])


def test_names():
    assert tr.program_name("jit__decode_resident(123456)") == (
        "_decode_resident")
    assert tr.program_name("main") == "main"
    assert tr.short_name("%fusion.3 = bf16[16]{0} fusion(...)") == "fusion.3"
    assert tr.kernel_name(
        "%ragged_paged_prefill_attention.5 = bf16[8] custom-call()") == (
        "ragged_paged_prefill_attention")


def test_no_device_plane_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})
