"""Packed ragged prefill waves (ISSUE 11): engine-level contracts.

The kernel-vs-reference parity grid lives in test_pallas_attention.py;
this file pins the ENGINE half of the tentpole:

- zero prefill padding on the ragged path (exact binary-ladder wave
  decomposition) where the row-bucketed path paid bucket rounding, as
  long as no ridge prices a small wave at a pass over the weights;
- the wave planner (ISSUE 34): the cheapest cover of a round by ladder
  rungs with a wave priced ``max(width, ridge_tokens)``: one padded
  wave where that beats a second pass, largest-fit's padding where the
  ridge lies under the smallest rung;
- greedy decode bit-identical with SWARMDB_RAGGED_PREFILL=1 vs 0 —
  including prompts long enough to split across waves (the tail chunk
  reads its head's pages back through the ragged kernel's prefix path);
- the compiled prefill variant count of the ragged plan is STRICTLY
  below the bucketed plan's (the warmup_call_plan acceptance number);
- warmup covers everything serving hits: no recompiles mid-traffic;
- prefix-cache hits ride the ragged waves as prefix_len descriptors
  (reuse counters move, outputs stay deterministic);
- flight-step records carry wave_kind + decode_kernel tags.
"""

import numpy as np
import pytest

import jax

from swarmdb_tpu.backend.engine import plan_ragged_waves
from swarmdb_tpu.backend.sampling import SamplingParams
from swarmdb_tpu.backend.service import build_backend_engine
from swarmdb_tpu.models.configs import get_config
from swarmdb_tpu.obs import TRACER

CFG = get_config("tiny-debug")
PROMPTS = [[1, 5, 9, 2, 7] * 3, [4] * 37, [7], [2, 3] * 11]


def _build(ragged: bool, monkeypatch):
    monkeypatch.setenv("SWARMDB_RAGGED_PREFILL", "1" if ragged else "0")
    eng, _tok = build_backend_engine(CFG, max_batch=4, max_seq=96,
                                     paged=True, page_size=16)
    return eng


def _greedy(eng, prompt, n=8):
    return eng.generate_sync(prompt, SamplingParams(max_new_tokens=n))


LADDER = [8 << i for i in range(10)]            # 8 ... 4096, the cell's
CPU_RIDGE = 2.5                                 # the CPU row, bf16


def largest_fit(n, ladder):
    """The planner before ISSUE 34: peel the largest rung that fits, pad
    only the last flush."""
    plan = []
    while n > 0:
        plan.append(next((w for w in reversed(ladder) if w <= n), ladder[0]))
        n -= plan[-1]
    return plan


def price(plan, ridge):
    return sum(max(w, ridge) for w in plan)


@pytest.mark.parametrize("ridge", [240, 64, CPU_RIDGE])
def test_wave_plan_covers_and_never_costs_more(ridge):
    """For every round size: the plan covers it with ladder rungs and
    costs no more than largest-fit and no more than rounding up."""
    for n in range(1, LADDER[-1] + 1):
        plan = plan_ragged_waves(n, LADDER, ridge)
        assert set(plan) <= set(LADDER) and sum(plan) >= n, (n, plan)
        assert sum(plan[:-1]) < n, (n, plan)        # no wave is all padding
        assert price(plan, ridge) <= price(largest_fit(n, LADDER), ridge)
        up = next(w for w in LADDER if w >= n)
        assert price(plan, ridge) <= max(up, ridge), (n, plan)


@pytest.mark.parametrize("n, plan", [
    (170, [256]), (100, [128]), (130, [256]), (350, [256, 128]),
    (2300, [2048, 256]), (5, [8])])
def test_wave_plan_at_the_v5e_ridge(n, plan):
    """bf16 on a v5e: 240 tokens a pass over the weights. A round's tail
    is rounded up; a long round keeps its full rungs."""
    assert plan_ragged_waves(n, LADDER, 240) == plan
    assert len(largest_fit(n, LADDER)) >= len(plan)


def test_wave_plan_under_the_smallest_rung_pads_like_largest_fit():
    """With the ridge under the smallest rung (a CPU) a wave costs its
    width: the plan dispatches exactly the tokens largest-fit did, in no
    more waves (equal cost goes to the single wave: 60 is [64], not
    32 + 16 + 8 + 8), and a rung is never rounded up past that."""
    for ladder in (LADDER, [8, 16, 32, 64, 96]):
        for n in range(1, ladder[-1] + 1):
            plan, old = plan_ragged_waves(n, ladder, CPU_RIDGE), largest_fit(
                n, ladder)
            assert sum(plan) == sum(old), (n, plan, old)
            assert len(plan) <= len(old)
    assert plan_ragged_waves(37, LADDER, CPU_RIDGE) == [32, 8]
    assert plan_ragged_waves(60, LADDER, CPU_RIDGE) == [64]
    # a round beyond the top rung takes top rungs first
    assert plan_ragged_waves(9000, LADDER, 240) == [4096, 4096, 512, 256, 64]
    # with no ridge and rungs down to 1 the cover is the binary
    # decomposition: nothing is padded
    fine = [1 << i for i in range(8)]
    for n in range(1, 256):
        assert sum(plan_ragged_waves(n, fine, 0.0)) == n


def test_ragged_engine_wiring(monkeypatch):
    eng = _build(True, monkeypatch)
    assert eng._ragged_active()
    # the CPU row of obs/profiler's peaks, float32 weights: under the
    # smallest rung, so a wave costs its width
    assert 0 < eng._ragged_ridge_tokens <= 8
    # power-of-two ladder from SWARMDB_RAGGED_MIN_WIDTH (default 8 —
    # one TPU sublane quantum; rungs below 8 compile programs the
    # dispatcher pads back up to 8 anyway, PROFILE.md round 11)
    assert eng._ragged_widths == [8, 16, 32, 64, 96]
    assert eng._ragged_width_for(96) == 96
    assert eng._ragged_width_for(37) == 32   # 32 + 8 beats one wave of 64
    assert eng._ragged_width_for(1) == 8     # final flush pads < min_w
    # the knob still widens the ladder down to exact-packing
    monkeypatch.setenv("SWARMDB_RAGGED_MIN_WIDTH", "1")
    fine = _build(True, monkeypatch)
    assert fine._ragged_widths == [1, 2, 4, 8, 16, 32, 64, 96]
    assert fine._ragged_width_for(1) == 1
    monkeypatch.delenv("SWARMDB_RAGGED_MIN_WIDTH")
    off = _build(False, monkeypatch)
    assert not off._ragged_active()
    # the row-bucketed fallback machinery stays intact under =0
    assert off._row_buckets == [1, 2, 4]


def test_ragged_zero_padding_and_exact_packing(monkeypatch):
    # exact binary decomposition is the min_width=1 contract; the
    # default floor of 8 trades <8 pad tokens per final flush for a
    # smaller compiled-variant set (covered by the wiring test above).
    # It holds where no ridge prices a wave of 1 or 2 tokens at a pass
    # over the weights (the CPU row's 2.5-5 tokens would round 3 up to 4)
    monkeypatch.setenv("SWARMDB_RAGGED_MIN_WIDTH", "1")
    eng = _build(True, monkeypatch)
    eng._ragged_ridge_tokens = 0.0
    c = eng.metrics.counters
    eng.start()
    try:
        for p in PROMPTS:
            _greedy(eng, p)
        assert c["prefill_padding_tokens"].value == 0
        assert c["prefill_packed_tokens"].value == sum(
            len(p) for p in PROMPTS)
    finally:
        eng.stop()
    # the flight record carries the wave-kind + decode-kernel tags
    steps = eng.flight.steps()
    assert any(s.get("wave_kind") == "ragged" for s in steps)
    assert all(s.get("decode_kernel") in ("pallas", "gather")
               for s in steps if "decode_kernel" in s)
    assert any("prefill_packed_tokens" in s for s in steps)


def test_ragged_greedy_bit_identical_to_bucketed(monkeypatch):
    """Acceptance: engine greedy decode is bit-identical with
    SWARMDB_RAGGED_PREFILL=1 vs 0 — same PRNG folds, same bf16 KV bytes,
    prompts spanning single-wave, multi-wave-split, and sub-page
    shapes."""
    from swarmdb_tpu.ops.paged_kv import kv_quantized
    if kv_quantized():
        # int8 pool: each admission path quantizes against its own
        # page-window contents, so cross-path bit-identity is a
        # plain-pool contract (tests/test_kv_quant.py pins the int8
        # drift floor instead)
        pytest.skip("bit-identity is a plain-pool (f32/bf16) contract")
    rag = _build(True, monkeypatch)
    buck = _build(False, monkeypatch)
    rag.start()
    buck.start()
    try:
        # 37 splits as 32 + 8 and 77 as 64 + 16; 61 is one wave of 64
        for p in PROMPTS + [[9] * 61, [8] * 77]:
            tr, rr = _greedy(rag, p, n=10)
            tb, rb = _greedy(buck, p, n=10)
            assert tr == tb, (p, tr, tb)
            assert rr == rb
    finally:
        rag.stop()
        buck.stop()


def test_ragged_plan_strictly_fewer_prefill_variants(monkeypatch):
    """Acceptance: compiled prefill variant count strictly below the
    bucketed plan's. The ragged plan's only prefill axis is the width
    ladder; the bucketed plan multiplies buckets x row buckets and adds
    the whole prefix (bucket x width x rows) family."""
    rag = _build(True, monkeypatch)
    buck = _build(False, monkeypatch)

    def prefill_entries(eng):
        decode = set(eng._decode_variants)
        if eng._resident_variants is not None:
            decode |= set(eng._resident_variants)
        return [fn for fn, _ in eng.warmup_call_plan() if fn not in decode]

    n_rag, n_buck = len(prefill_entries(rag)), len(prefill_entries(buck))
    assert n_rag == len(rag._ragged_widths)
    assert n_rag < n_buck, (n_rag, n_buck)


def test_ragged_warmup_covers_serving(monkeypatch):
    """No cold compiles mid-traffic: after warmup, serving mixed shapes
    (splits, prefix hits, sub-page prompts) adds ZERO compiled
    variants."""
    eng = _build(True, monkeypatch)
    eng.warmup()
    n0 = eng._compiled_count()
    assert n0 >= len(eng._ragged_widths)
    eng.start()
    try:
        for p in PROMPTS:
            _greedy(eng, p)
        _greedy(eng, PROMPTS[0])         # prefix-cache hit wave
    finally:
        eng.stop()
    assert eng._compiled_count() == n0


def test_ragged_prefix_hits_ride_the_waves(monkeypatch):
    """A repeated prompt's second admission reuses its registered pages
    as a prefix_len descriptor: reuse counters move, padding stays zero,
    and greedy output is unchanged."""
    eng = _build(True, monkeypatch)
    c = eng.metrics.counters
    eng.start()
    try:
        prompt = [3, 1, 4, 1, 5, 9, 2, 6] * 5   # 40 tokens = 2.5 pages
        t1, _ = _greedy(eng, prompt)
        assert c["prefix_reused_tokens"].value == 0
        t2, _ = _greedy(eng, prompt)
        assert c["prefix_reused_tokens"].value == 32  # 2 full pages
        assert t2 == t1
        assert c["prefill_padding_tokens"].value == 0
    finally:
        eng.stop()


def test_ridge_plans_fewer_waves_same_tokens(monkeypatch):
    """ISSUE 34 on the engine: with the ridge at 64 tokens every round
    of the file's prompts is one padded wave. The greedy tokens are
    those of largest-fit, the dispatches fewer, the padding what the
    pack phases say, and no program beyond warm-up's is compiled."""
    old = _build(True, monkeypatch)
    old._ragged_width_for = lambda n: largest_fit(n, old._ragged_widths)[0]
    new = _build(True, monkeypatch)
    new._ragged_ridge_tokens = 64.0
    assert [new._ragged_width_for(len(p)) for p in PROMPTS] == [16, 64, 8, 32]
    new.warmup()
    n0 = new._compiled_count()
    warm_waves = new.metrics.counters["prefill_device_waves"].value
    TRACER.reset()
    was = TRACER.enabled
    TRACER.set_enabled(True)
    old.start()
    new.start()
    try:
        # the last prompt finds 48 of its 77 tokens cached: a round of 29
        prompts = PROMPTS + [[9] * 61, [9] * 77]
        for p in prompts:
            assert _greedy(new, p, n=10) == _greedy(old, p, n=10), p
    finally:
        old.stop()
        new.stop()
        TRACER.set_enabled(was)
    assert new._compiled_count() == n0
    co, cn = old.metrics.counters, new.metrics.counters
    assert (cn["prefill_device_waves"].value - warm_waves == len(prompts)
            < co["prefill_device_waves"].value)
    assert (cn["prefill_packed_tokens"].value
            == co["prefill_packed_tokens"].value
            == sum(len(p) for p in prompts) - 48)
    assert cn["prefill_padding_tokens"].value == 1 + 27 + 7 + 10 + 3 + 3
    assert co["prefill_padding_tokens"].value < 8 * len(prompts)
    packs = [e["args"] for e in TRACER.snapshot()
             if e["name"] == "engine.admission.pack"]
    assert sum(a["width"] - a["filled"] for a in packs) == (
        cn["prefill_padding_tokens"].value
        + co["prefill_padding_tokens"].value)
