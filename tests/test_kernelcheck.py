"""swarmkern tests (ISSUE 16): static SWL901-905 + the runtime shadow.

Static half: the kernel family's fixture findings, revisit-directive
semantics, and the symbolic VMEM machinery the profiler integration
rides on. Runtime half: the interpreter-mode sanitizer's full
contract — flag-off type identity, seeded-crime detection (canary
short-write, bounds-checked Refs naming the grid cell, grid write
races, wave-descriptor audits), kernel-vs-reference differential
parity, and the dump/metrics/report surface the CI drill scans.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from swarmdb_tpu.analysis import analyze_file
from swarmdb_tpu.analysis.kernelcheck import (estimate_vmem,
                                              static_vmem_table,
                                              vmem_budget)

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures", "lint")


# ---------------------------------------------------------------------------
# static layer (analysis/kernelcheck.py)


@pytest.mark.parametrize("fixture,rule", [
    ("kernel_oob_bad.py", "SWL901"),
    ("kernel_race_bad.py", "SWL902"),
    ("kernel_vmem_bad.py", "SWL903"),
    ("kernel_tile_bad.py", "SWL904"),
    ("kernel_unwritten_bad.py", "SWL905"),
])
def test_kernel_family_fixture_findings(fixture, rule):
    rules = {f.rule for f in analyze_file(os.path.join(FIXTURES, fixture))}
    assert rules == {rule}


def test_revisit_directive_sanctions_accumulate(tmp_path):
    """The ``# swarmlint: revisit[<dim>]`` directive is the ONLY thing
    separating the two wrappers in the race fixture: the undeclared one
    fires SWL902, the declared accumulate stays quiet — and declaring
    the WRONG dim sanctions nothing."""
    src = open(os.path.join(FIXTURES, "kernel_race_bad.py")).read()
    findings = analyze_file(os.path.join(FIXTURES, "kernel_race_bad.py"))
    assert [f.rule for f in findings] == ["SWL902"]
    # one finding: racing_rows only — sanctioned_rows is covered
    assert all(f.line == 24 for f in findings)
    # revisit[j] does not sanction a revisit over dim r
    bad = tmp_path / "wrong_dim.py"
    bad.write_text(src.replace("revisit[r]", "revisit[j]"))
    assert {f.rule for f in analyze_file(str(bad))} == {"SWL902"}
    assert len(analyze_file(str(bad))) == 2


def test_in_tree_kernels_are_clean():
    """ops/attention_pallas.py under the full kernel family: zero
    findings (its deliberate accumulate carries the revisit
    directive)."""
    import swarmdb_tpu.ops.attention_pallas as ap

    assert analyze_file(ap.__file__) == []


def test_static_vmem_table_covers_in_tree_kernels():
    rows = static_vmem_table()
    kernels = {r["kernel"] for r in rows}
    assert "_ragged_prefill_kernel" in kernels
    assert "_paged_chunk_attn_kernel" in kernels
    for r in rows:
        assert r["formula"]
        assert r["expr"] is not None


def test_static_vmem_of_the_chunked_decode_page_loop():
    """The chunked paged decode kernel leaves its pools in HBM (ANY
    space, no block) and copies pages into scratch of the pool's dtype,
    and holds a whole group of slots' queries, chunk buffers and outputs
    as blocks: its row is in the table, and under the dims its
    dispatcher binds the page buffers are what `_pages_per_block` makes
    them and the blocks a batch's, up to `_ROW_GROUP` slots."""
    from swarmdb_tpu.ops.attention_pallas import (_ROW_GROUP,
                                                  _pages_per_block)

    rows = {r["kernel"]: r for r in static_vmem_table()}
    assert "_paged_chunk_attn_kernel" in rows
    hq, hkv, d, ps, kc, maxp = 32, 8, 128, 16, 8, 256
    for b, itemsize in ((16, 2), (32, 2), (40, 2), (16, 4)):
        dims = {"B": b, "Hq": hq, "Hkv": hkv, "D": d, "ps": ps, "Kc": kc,
                "maxp": maxp, "itemsize": itemsize}
        ppb = _pages_per_block(ps, hkv, d, itemsize, maxp)
        buffers = 2 * 2 * ppb * ps * hkv * d * itemsize   # K, V halves
        blocks = (2 * 4 * min(b, _ROW_GROUP)              # q, out, chunks
                  * (2 * hq * d + 2 * kc * hkv * d))
        state = 4 * hq * (d + 2 * 128)                    # acc, max, denom
        assert estimate_vmem("_paged_chunk_attn_kernel",
                             dims) == buffers + blocks + state
    assert _pages_per_block(16, 8, 128, 2, 256) == 8      # the chat cell
    assert _pages_per_block(16, 8, 128, 2, 4) == 4        # a short table
    assert _pages_per_block(256, 8, 128, 2, 16) == 1      # a page a block
    assert estimate_vmem("_paged_chunk_attn_kernel", {"Hq": hq}) is None


def test_static_vmem_of_the_ragged_prefill_walk():
    """The ragged prefill kernel leaves the pools and the suffix stream
    in HBM (ANY space) and holds one query block, its state and the two
    halves of one key block: its row is in the table under its own name
    (not its `_quant` twin's), and under the dims its dispatcher binds
    the buffers are what `_pages_per_block` and the tile make them."""
    from swarmdb_tpu.ops.attention_pallas import _pages_per_block

    hq, hkv, d, ps, maxp = 32, 8, 128, 16, 256
    for w, itemsize in ((128, 2), (4096, 2), (16, 2), (128, 4)):
        dims = {"W": w, "tile": 128, "Hq": hq, "Hkv": hkv, "D": d,
                "ps": ps, "maxp": maxp, "itemsize": itemsize}
        tq = min(128, w)
        keys = max(_pages_per_block(ps, hkv, d, itemsize, maxp) * ps, tq)
        buffers = 2 * 2 * keys * hkv * d * itemsize       # K, V halves
        blocks = 2 * 4 * 2 * tq * hq * d                  # q, out
        state = 4 * tq * hq * (d + 2 * 128)               # acc, max, denom
        assert estimate_vmem("_ragged_prefill_kernel",
                             dims) == buffers + blocks + state
    # without the table's width and the pool's item size: no estimate,
    # and never the twin's
    dims = {"W": 64, "tile": 128, "Hq": 8, "Hkv": 2, "D": 64, "ps": 16}
    assert estimate_vmem("_ragged_prefill_kernel", dims) is None
    assert estimate_vmem("_ragged_prefill_kernel_quant", dims) > 0


def test_estimate_vmem_concrete_and_unbound():
    dims = {"W": 64, "tile": 128, "Hq": 8, "Hkv": 2, "D": 64, "ps": 16,
            "maxp": 64, "itemsize": 2}
    est = estimate_vmem("_ragged_prefill_kernel", dims)
    assert isinstance(est, int) and est > 0
    # unbound dims -> no estimate, never an error
    assert estimate_vmem("_ragged_prefill_kernel", {"W": 64}) is None
    assert estimate_vmem("no_such_kernel", dims) is None


def test_vmem_budget_platforms_and_override(monkeypatch):
    monkeypatch.delenv("SWARMDB_VMEM_BYTES", raising=False)
    assert vmem_budget("TPU v6 lite") == 32 * 1024 * 1024
    assert vmem_budget("TPU v5e") == 16 * 1024 * 1024
    assert vmem_budget("") == 16 * 1024 * 1024
    monkeypatch.setenv("SWARMDB_VMEM_BYTES", "1234567")
    assert vmem_budget("TPU v6 lite") == 1234567


# ---------------------------------------------------------------------------
# runtime layer (obs/kerncheck.py)


@pytest.fixture()
def kerncheck_on(monkeypatch, tmp_path):
    """Enable the sanitizer with a scratch dump dir and a clean
    registry; always reset afterwards so deliberately-provoked
    violations never leak into the session-level zero-violation
    assertion (conftest.pytest_sessionfinish)."""
    monkeypatch.setenv("SWARMDB_KERNCHECK", "1")
    monkeypatch.setenv("SWARMDB_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("SWARMDB_NODE_ID", "testnode")
    from swarmdb_tpu.obs import kerncheck

    kerncheck.registry().reset()
    yield kerncheck
    kerncheck.registry().reset()


def _unwrapped(fn):
    """The plain dispatcher under whatever the CI kerncheck job wrapped
    it in at import."""
    while hasattr(fn, "__wrapped__"):
        fn = fn.__wrapped__
    return fn


def test_factories_return_plain_functions_when_off(monkeypatch):
    """The zero-overhead contract: flag off = the checked factories hand
    back the exact function objects they were given (type identity, not
    a pass-through wrapper)."""
    monkeypatch.delenv("SWARMDB_KERNCHECK", raising=False)
    from swarmdb_tpu.obs import kerncheck

    def fn(*a, **k):
        return None

    assert kerncheck.checked_ragged_prefill_dispatch(fn) is fn
    assert kerncheck.checked_paged_attention_dispatch_chunked(fn) is fn
    assert kerncheck.checked_paged_write_ragged(fn) is fn
    assert kerncheck.checked_ssm_state_read(fn) is fn


def test_dispatch_module_binding_matches_flag():
    """ops.layers / ops.paged_kv rebind their dispatchers through the
    checked factories exactly when the env flag was set at import: the
    tier-1 run sees the plain functions, the CI kerncheck job sees the
    wrappers."""
    from swarmdb_tpu.models import nemotron_h
    from swarmdb_tpu.ops import layers, paged_kv

    wrapped = os.environ.get("SWARMDB_KERNCHECK", "0") == "1"
    assert hasattr(nemotron_h.ssm_state_read, "__wrapped__") == wrapped
    assert hasattr(layers.ragged_prefill_dispatch, "__wrapped__") \
        == wrapped
    assert hasattr(layers.paged_attention_dispatch_chunked,
                   "__wrapped__") == wrapped
    assert hasattr(paged_kv.paged_write_ragged, "__wrapped__") == wrapped


def test_shadow_clean_on_in_tree_kernels(kerncheck_on):
    """The in-tree ragged prefill and paged decode kernels commit no
    kernel crimes under the shadow interpreter, and the shadow output
    matches the dense reference on live tokens."""
    from swarmdb_tpu.ops.layers import ragged_prefill_attention_reference

    rng = np.random.default_rng(7)
    (q, sk, sv, kp, vp, tables, starts, lens, plens,
     tok_row) = kerncheck_on._random_ragged_case(rng)
    out = kerncheck_on.shadow_ragged_prefill(
        q, sk, sv, kp, vp, tables, starts, lens, plens)
    assert kerncheck_on.registry().violations() == []
    want = np.asarray(ragged_prefill_attention_reference(
        q, sk, sv, kp, vp, tables, starts, lens, plens,
        jnp.asarray(tok_row)))
    live = tok_row < np.asarray(tables).shape[0]
    assert float(np.max(np.abs(out[live] - want[live]))) < 2e-2


def test_canary_fires_on_seeded_short_write(kerncheck_on, tmp_path):
    """A sabotaged kernel that loses one live row's finalize leaves that
    row either canaried or only-zero-filled — a short-write violation
    naming the row, dumped SIGKILL-proof the moment it is recorded."""
    from jax.experimental import pallas as pl

    rng = np.random.default_rng(3)
    (q, sk, sv, kp, vp, tables, starts, lens, plens,
     _tok_row) = kerncheck_on._random_ragged_case(rng)
    live_r = int(np.nonzero(np.asarray(lens) > 0)[0][0])
    base = kerncheck_on.ragged_prefill_body(kp, tables)

    def sabotaged(*refs):
        # grid (query block, row): whatever row ``live_r``'s step wrote
        # into the output block (refs[9]) is taken back
        o_ref = refs[9]
        before = o_ref[...]
        base(*refs)
        if pl.program_id(1) == live_r:
            o_ref[...] = before

    kerncheck_on.shadow_ragged_prefill(
        q, sk, sv, kp, vp, tables, starts, lens, plens,
        kernel=sabotaged)
    vs = kerncheck_on.registry().violations()
    assert {v["kind"] for v in vs} == {"short-write"}
    assert any(f"row {live_r}" in v["message"] for v in vs)
    assert all(v["rule"] == "SWL905" for v in vs)
    dump = json.loads((tmp_path / "kerncheck_testnode.json").read_text())
    assert dump["violations"]


def test_bounds_wrapper_names_grid_cell(kerncheck_on):
    """An in-kernel Ref access past the block records an oob-ref naming
    the ref, the slice, and the grid cell it happened at — then clamps
    so the run finishes and surfaces everything at once."""
    from jax.experimental import pallas as pl

    rng = np.random.default_rng(5)
    (q, sk, sv, kp, vp, tables, starts, lens, plens,
     _tok_row) = kerncheck_on._random_ragged_case(rng)
    base = kerncheck_on.ragged_prefill_body(kp, tables)

    def overread(*refs):
        if pl.program_id(0) == 0 and pl.program_id(1) == 0:
            q_ref = refs[4]          # after the 4 scalar-prefetch refs
            _ = q_ref[pl.ds(0, q_ref.shape[0] + 4), ...]
        base(*refs)

    kerncheck_on.shadow_ragged_prefill(
        q, sk, sv, kp, vp, tables, starts, lens, plens, kernel=overread)
    kinds = {v["kind"] for v in kerncheck_on.registry().violations()}
    assert "oob-ref" in kinds
    v = next(v for v in kerncheck_on.registry().violations()
             if v["kind"] == "oob-ref")
    assert "grid cell (0, 0)" in v["message"]
    assert v["where"]["grid"] == [0, 0]
    assert v["rule"] == "SWL901"


def test_write_race_on_unmasked_finalize(kerncheck_on):
    """Dropping the row mask from the finalize makes every row step
    rewrite the whole output block — the element-granular last-writer
    map calls the collision between the grid's ROW steps."""
    from jax.experimental import pallas as pl

    rng = np.random.default_rng(11)
    (q, sk, sv, kp, vp, tables, starts, lens, plens,
     _tok_row) = kerncheck_on._random_ragged_case(rng)
    base = kerncheck_on.ragged_prefill_body(kp, tables)

    def unmasked(*refs):
        base(*refs)
        o_ref = refs[9]
        # rogue: EVERY row step rewrites the whole output block with a
        # value that varies by grid row, so later rows overwrite bytes
        # the earlier rows just wrote
        o_ref[...] = jnp.zeros_like(o_ref[...]) + 1.5 * (
            pl.program_id(1) + 1)

    kerncheck_on.shadow_ragged_prefill(
        q, sk, sv, kp, vp, tables, starts, lens, plens, kernel=unmasked)
    kinds = {v["kind"] for v in kerncheck_on.registry().violations()}
    assert "write-race" in kinds


def test_wave_descriptor_checks(kerncheck_on):
    """check_wave_descriptors: OOB page ids, live tokens aimed at trash
    page 0, and duplicate (page, offset) cells are each one named
    violation; the dead-token padding the engine builds is ignored."""
    R, maxp, ps, P = 3, 2, 4, 8
    tables = np.array([[3, 4], [5, 6], [7, 2]], np.int32)
    # clean wave (incl. dead padding row R / overshoot positions)
    n = kerncheck_on.check_wave_descriptors(
        np.array([0, 1, 2, R], np.int32),
        np.array([0, 5, 7, ps * maxp], np.int32), tables, P, ps)
    assert n == 0
    # oob page id
    bad = tables.copy()
    bad[1, 1] = P + 3
    n = kerncheck_on.check_wave_descriptors(
        np.array([1], np.int32), np.array([ps], np.int32), bad, P, ps)
    assert n == 1
    vs = kerncheck_on.registry().violations()
    assert vs[-1]["kind"] == "oob-block" and vs[-1]["rule"] == "SWL901"
    # live token into trash page 0
    zero = np.zeros((R, maxp), np.int32)
    n = kerncheck_on.check_wave_descriptors(
        np.array([0], np.int32), np.array([1], np.int32), zero, P, ps)
    assert n == 1
    assert kerncheck_on.registry().violations()[-1]["kind"] == "oob-block"
    # two live tokens on one (page, offset) cell
    n = kerncheck_on.check_wave_descriptors(
        np.array([0, 0], np.int32), np.array([1, 1], np.int32),
        tables, P, ps)
    assert n == 1
    assert kerncheck_on.registry().violations()[-1]["kind"] == "write-race"
    assert kerncheck_on.registry().violations()[-1]["rule"] == "SWL902"


def test_checked_write_replay_parity_clean(kerncheck_on):
    """checked_paged_write_ragged on the real op: descriptor audit plus
    numpy scatter replay agree with the jax result — zero violations."""
    from swarmdb_tpu.ops.paged_kv import paged_write_ragged

    rng = np.random.default_rng(1)
    L, P, ps, Hkv, D, R, maxp = 2, 10, 4, 2, 8, 3, 3
    kp = jnp.asarray(rng.standard_normal((L, P, ps, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((L, P, ps, Hkv, D)), jnp.float32)
    sk = jnp.asarray(rng.standard_normal((L, 8, Hkv, D)), jnp.float32)
    sv = jnp.asarray(rng.standard_normal((L, 8, Hkv, D)), jnp.float32)
    tables = jnp.asarray(
        np.array([[3, 4, 0], [5, 0, 0], [6, 7, 0]], np.int32))
    tok_row = jnp.asarray(np.array([0, 0, 1, 1, 1, 2, 5, 5], np.int32))
    tok_pos = jnp.asarray(np.array([3, 4, 0, 1, 2, 7, 0, 0], np.int32))
    base = _unwrapped(paged_write_ragged)
    f = kerncheck_on.checked_paged_write_ragged(base)
    assert f is not base                     # flag on: wrapped
    f(kp, vp, sk, sv, tok_row, tok_pos, tables)
    assert kerncheck_on.registry().violations() == []
    assert kerncheck_on.registry().report()["checks"][
        "shadow.paged-write-ragged"] == 1


def test_differential_parity_in_tree(kerncheck_on):
    """Randomized kernel-vs-reference differentials (mixed lens, page
    crossings, empty rows, splits; for the chunked decode kernel empty
    slots off the live-row list and a chunk buffer part full): zero
    mismatching rounds, zero violations."""
    assert kerncheck_on.differential_ragged_prefill(seed=0, rounds=2) == 0
    assert kerncheck_on.differential_paged_decode(seed=0, rounds=2) == 0
    assert kerncheck_on.registry().violations() == []
    checks = kerncheck_on.registry().report()["checks"]
    assert checks["differential.ragged-prefill"] == 2
    assert checks["differential.paged-decode"] == 2


def test_differential_paged_decode_int8_in_tree(kerncheck_on):
    """The int8 twin of the chunked decode kernel, which walks every
    slot, against the quantized gather path: zero mismatching rounds."""
    assert kerncheck_on.differential_paged_decode(
        seed=3, rounds=3, quantized=True) == 0
    assert kerncheck_on.registry().violations() == []
    assert kerncheck_on.registry().report()["checks"][
        "differential.paged-decode.int8"] == 3


def test_differential_paged_decode_catches_a_row_left_off_the_walk(
        kerncheck_on, monkeypatch):
    """The differential can fail: a kernel handed one live row fewer
    than the table holds leaves that row zero, and every round is a
    parity mismatch naming the chunked kernel."""
    from swarmdb_tpu.ops import paged_kv

    real = paged_kv.live_row_list

    def one_short(table):
        rows, n_live = real(table)
        return rows, n_live - 1

    monkeypatch.setattr(paged_kv, "live_row_list", one_short)
    assert kerncheck_on.differential_paged_decode(seed=0, rounds=2) == 2
    (v, *_rest) = kerncheck_on.registry().violations()
    assert v["kind"] == "parity"
    assert v["kernel"] == "paged_decode_gqa_attention_chunked"


def _chunked_dispatch_case(seed=5, quantized=False):
    """Concrete operands of ``paged_attention_dispatch_chunked``: four
    slots, slot 2 holds no sequence (all-trash table row) and is off the
    live-row list; prefixes mid-page, page-aligned and empty; step 1 of a
    chunk of 4."""
    from swarmdb_tpu.ops.paged_kv import (QuantPool, _quantize_pages,
                                          live_row_list)

    rng = np.random.default_rng(seed)
    B, Hkv, G, D, ps, maxp, Kc = 4, 2, 2, 8, 4, 3, 4
    P = 1 + B * maxp
    table = np.arange(1, 1 + B * maxp, dtype=np.int32).reshape(B, maxp)
    table[2] = 0
    starts = np.asarray([ps + 1, 2 * ps, 0, 0], np.int32)
    step = jnp.int32(1)
    f32 = lambda *shape: jnp.asarray(rng.standard_normal(shape),
                                     jnp.float32)
    kp, vp = f32(P, ps, Hkv, D), f32(P, ps, Hkv, D)
    if quantized:
        kp, vp = (QuantPool(*_quantize_pages(p)) for p in (kp, vp))
    table = jnp.asarray(table)
    return dict(
        args=(f32(B, 1, Hkv * G, D), kp, vp, table, f32(B, Kc, Hkv, D),
              f32(B, Kc, Hkv, D), jnp.asarray(starts + 1)[:, None], step),
        live_rows=live_row_list(table), dead=2)


def _plain_chunked_dispatch():
    from swarmdb_tpu.ops import layers

    return _unwrapped(layers.paged_attention_dispatch_chunked)


@pytest.mark.parametrize("quantized", [False, True], ids=["bf16", "int8"])
def test_checked_chunked_dispatch_clean_on_the_kernel(kerncheck_on,
                                                      monkeypatch,
                                                      quantized):
    """The checker on the dispatch the cells run (SWARMDB_PALLAS=1: the
    kernel, interpreted off the chip): live rows agree with the gather
    form, the slot off the list reads exact zeros from the kernel that
    walks the list, no violation; one check is tallied a call."""
    monkeypatch.setenv("SWARMDB_PALLAS", "1")
    if quantized:
        monkeypatch.setenv("SWARMDB_KV_DTYPE", "int8")
    case = _chunked_dispatch_case(quantized=quantized)
    f = kerncheck_on.checked_paged_attention_dispatch_chunked(
        _plain_chunked_dispatch())
    assert f is not _plain_chunked_dispatch()    # flag on: wrapped
    out = np.asarray(f(*case["args"], live_rows=case["live_rows"]))
    assert kerncheck_on.registry().violations() == []
    assert kerncheck_on.registry().report()["checks"][
        "dispatch.paged-decode-chunked"] == 1
    assert out[case["dead"]].any() == quantized   # int8: every slot walked
    # where the dispatch gathers, the check runs the kernel itself on the
    # same operands and holds that to the gather form
    monkeypatch.setenv("SWARMDB_PALLAS", "0")
    f(*case["args"], live_rows=case["live_rows"])
    assert kerncheck_on.registry().violations() == []
    assert kerncheck_on.registry().report()["checks"][
        "dispatch.paged-decode-chunked"] == 2


@pytest.mark.parametrize("planted,kind", [
    ("dead-row", "dead-row"), ("live-row", "parity")])
def test_checked_chunked_dispatch_catches_planted_faults(kerncheck_on,
                                                         monkeypatch,
                                                         planted, kind):
    """A dispatch that writes a slot it was not handed breaks the
    kernel's contract (exact zeros off the live-row list); one that
    returns a wrong live row is a parity violation. Each names the
    chunked kernel."""
    monkeypatch.setenv("SWARMDB_PALLAS", "1")
    case = _chunked_dispatch_case()
    base = _plain_chunked_dispatch()
    row = case["dead"] if planted == "dead-row" else 0

    def rogue(*args, **kw):
        return base(*args, **kw).at[row, 0, 0, 0].add(0.5)

    f = kerncheck_on.checked_paged_attention_dispatch_chunked(rogue)
    f(*case["args"], live_rows=case["live_rows"])
    (v,) = kerncheck_on.registry().violations()
    assert v["kind"] == kind
    assert v["kernel"] == "paged_decode_gqa_attention_chunked"


def test_checked_chunked_dispatch_checks_the_kernel_where_it_gathered(
        kerncheck_on, monkeypatch):
    """SWARMDB_PALLAS=0 (as on the CPU without the flag, or under the
    kv-span threshold): the dispatch returns the gather form, and the
    check is still of the kernel, never of the gather form against
    itself: a kernel that leaves a live row wrong is a parity violation
    there too."""
    from swarmdb_tpu.ops import attention_pallas

    monkeypatch.setenv("SWARMDB_PALLAS", "0")
    real = attention_pallas.paged_decode_gqa_attention_chunked
    monkeypatch.setattr(
        attention_pallas, "paged_decode_gqa_attention_chunked",
        lambda *a, **kw: real(*a, **kw).at[0, 0, 0].add(0.5))
    case = _chunked_dispatch_case()
    f = kerncheck_on.checked_paged_attention_dispatch_chunked(
        _plain_chunked_dispatch())
    f(*case["args"], live_rows=case["live_rows"])
    (v,) = kerncheck_on.registry().violations()
    assert v["kind"] == "parity"
    assert v["kernel"] == "paged_decode_gqa_attention_chunked"


@pytest.mark.parametrize("planted,kind", [
    (None, None), ("dead-row", "dead-row"), ("live-row", "parity")])
def test_checked_ssm_state_read(kerncheck_on, monkeypatch, planted, kind):
    """The Mamba-2 state-read kernel (interpreted here, on a bf16 copy of
    the pool) held to the batch-wide form on every concrete call: clean
    as it is; a kernel that writes a slot off the list, or a live one
    wrong, is named."""
    from swarmdb_tpu.models import nemotron_h
    from swarmdb_tpu.models.configs import get_config
    from swarmdb_tpu.ops import ssm_pallas

    cfg = get_config("tiny-nemotron", ssm_heads=4, ssm_head_dim=64,
                     ssm_groups=2, ssm_state=128)
    rng = np.random.default_rng(5)
    pool = jnp.asarray(rng.standard_normal((2, 4, 256, 128)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((4, 2, 128)), jnp.float32)
    rows, n_live = jnp.asarray([3, 1, 0, 2], jnp.int32), jnp.int32(2)
    if planted:
        real = ssm_pallas.state_read
        row = 0 if planted == "dead-row" else 3
        monkeypatch.setattr(
            ssm_pallas, "state_read",
            lambda *a, **kw: real(*a, **kw).at[row, 0].add(0.5))
    f = kerncheck_on.checked_ssm_state_read(
        _unwrapped(nemotron_h.ssm_state_read))
    f(cfg, pool, jnp.int32(1), Cm, rows, n_live)
    assert kerncheck_on.registry().report()["checks"][
        "dispatch.ssm-state-read"] == 1
    found = kerncheck_on.registry().violations()
    assert [v["kind"] for v in found] == ([kind] if kind else [])
    assert all(v["kernel"] == "ssm_state_read" for v in found)


def test_checked_dispatch_catches_wrong_output(kerncheck_on):
    """The checked dispatcher compares the dispatched result against the
    shadow: a dispatch that returns garbage is a parity violation."""
    rng = np.random.default_rng(2)
    (q, sk, sv, kp, vp, tables, starts, lens, plens,
     tok_row) = kerncheck_on._random_ragged_case(rng)

    def rogue_dispatch(q, sfx_k, sfx_v, k_pages, v_pages, row_tables,
                       starts, lens, prefix_lens, tok_row, *,
                       window=None):
        return jnp.zeros_like(q) + 42.0

    f = kerncheck_on.checked_ragged_prefill_dispatch(rogue_dispatch)
    f(q, sk, sv, kp, vp, tables, starts, lens, plens,
      jnp.asarray(tok_row))
    kinds = {v["kind"] for v in kerncheck_on.registry().violations()}
    assert "parity" in kinds


def test_report_prometheus_and_dump_contract(kerncheck_on, tmp_path):
    reg = kerncheck_on.registry()
    reg.note_check("shadow.ragged-prefill")
    text = "\n".join(reg.prometheus_lines())
    assert "swarmdb_kernel_violations_total 0" in text
    assert ('swarmdb_kernel_checks_total{check="shadow.ragged-prefill"}'
            ' 1') in text
    reg.record("oob-block", "k", "seeded", {"grid": [1, 2]})
    text = "\n".join(reg.prometheus_lines())
    assert "swarmdb_kernel_violations_total 1" in text
    # record() dumped immediately (SIGKILL-proof), not just atexit
    dump_path = tmp_path / "kerncheck_testnode.json"
    assert dump_path.exists()
    dump = json.loads(dump_path.read_text())
    assert dump["violations"][0]["kind"] == "oob-block"
    assert dump["violations"][0]["rule"] == "SWL901"
    rep = reg.report()
    assert rep["enabled"] is True and rep["node"] == "testnode"
    # dedup: the same (kind, kernel, site) records once
    reg.record("oob-block", "k", "seeded", {"grid": [1, 2]})
    assert len(reg.violations()) == 1


def test_violation_emits_flight_instant(kerncheck_on):
    class FakeFlight:
        def __init__(self):
            self.events = []

        def record_event(self, ev):
            self.events.append(ev)

    fl = FakeFlight()
    reg = kerncheck_on.registry()
    reg.attach_flight(fl)
    reg.record("short-write", "kern", "seeded short write", {"row": 1})
    assert fl.events and fl.events[0]["kind"] == "kerncheck.violation"
    assert fl.events[0]["violation_kind"] == "short-write"
    assert fl.events[0]["rule"] == "SWL905"


def test_admin_endpoint_503_off_and_report_on(kerncheck_on):
    """/admin/kerncheck mirrors the lockcheck/pagecheck contract: 503
    with the flag off (an empty report must not read as 'no kernel
    bugs'), the registry report with it on."""
    from swarmdb_tpu.obs.kerncheck import enabled

    assert enabled() is True
    os.environ["SWARMDB_KERNCHECK"] = "0"
    try:
        assert enabled() is False
    finally:
        os.environ["SWARMDB_KERNCHECK"] = "1"
    app_src = open(os.path.join(
        os.path.dirname(__file__), "..", "swarmdb_tpu", "api",
        "app.py")).read()
    assert '"/admin/kerncheck"' in app_src
    assert "kernel sanitizer off" in app_src


def test_analyzer_lists_kerncheck_dumps_next_to_flight_dumps(
        kerncheck_on, tmp_path):
    """obs/analyze.py: a kerncheck dump sitting beside the analyzed
    trace shows up in the report with its violation count/kinds."""
    kerncheck_on.registry().record(
        "write-race", "paged_write_ragged", "seeded", {"cells": [5]})
    assert (tmp_path / "kerncheck_testnode.json").exists()

    from swarmdb_tpu.obs.analyze import _synthetic_trace, analyze_files

    trace_path = tmp_path / "trace.json"
    trace_path.write_text(json.dumps(
        {"traceEvents": _synthetic_trace(5.0, 10.0, 20.0)}))
    report = analyze_files([str(trace_path)])
    dumps = report.get("kerncheck_dumps")
    assert dumps and dumps[0]["violations"] == 1
    assert dumps[0]["node"] == "testnode"
    assert dumps[0]["violation_kinds"] == ["write-race"]
    assert dumps[0]["kernels"] == ["paged_write_ragged"]


def test_profiler_folds_static_vmem_estimates():
    """swarmprof: record_vmem_estimate is a SIDE table (not a harvest)
    merged into the variant rows by exact key or kernel:<tag> alias."""
    from swarmdb_tpu.obs.profiler import KernelProfiler

    prof = KernelProfiler(enabled=True)
    prof.record_variant("prefill.ragged[w64]", 1e9, 1e6)
    prof.record_variant("decode[b4]", 2e9, 2e6, meta={"kernel": "pallas"})
    prof.record_vmem_estimate("prefill.ragged[w64]", 4 << 20, 16 << 20)
    prof.record_vmem_estimate("kernel:pallas", 1 << 20, 16 << 20)
    rows = {r["variant"]: r for r in prof.variants_report()}
    assert rows["prefill.ragged[w64]"]["vmem_est_bytes"] == 4 << 20
    assert rows["prefill.ragged[w64]"]["vmem_utilization"] == 0.25
    assert rows["decode[b4]"]["vmem_est_bytes"] == 1 << 20
    assert rows["decode[b4]"]["vmem_budget_bytes"] == 16 << 20
    # the side table does NOT mark the variant harvested
    prof2 = KernelProfiler(enabled=True)
    prof2.record_vmem_estimate("prefill.ragged[w8]", 1, 2)
    assert prof2.harvested("prefill.ragged[w8]") is False
    assert prof2.harvest_calls == 0
    # reset clears it
    prof.reset()
    assert prof.variants_report() == []


def test_dispatch_records_vmem_estimate_under_profiler(monkeypatch):
    """ops.layers._record_static_vmem at dispatch trace time: the
    profiled ragged prefill variant carries its static footprint vs
    the platform budget in the variants report."""
    monkeypatch.setenv("SWARMDB_VMEM_BYTES", str(16 << 20))
    import sys

    from swarmdb_tpu.obs.profiler import KernelProfiler
    from swarmdb_tpu.ops import layers

    # the obs package re-exports the profiler() FUNCTION under the same
    # name — reach the module itself for the lazy global
    profmod = sys.modules["swarmdb_tpu.obs.profiler"]

    prof = KernelProfiler(enabled=True)
    monkeypatch.setattr(profmod, "_PROFILER", prof, raising=False)
    dims = {"W": 16, "tile": 128, "Hq": 4, "Hkv": 2, "D": 8, "ps": 4,
            "maxp": 8, "itemsize": 4}
    layers._record_static_vmem("_ragged_prefill_kernel",
                               "prefill.ragged[w16]", dims)
    prof.record_variant("prefill.ragged[w16]", 1.0, 1.0)
    row = next(r for r in prof.variants_report()
               if r["variant"] == "prefill.ragged[w16]")
    assert row["vmem_est_bytes"] == estimate_vmem(
        "_ragged_prefill_kernel", dims)
    assert row["vmem_budget_bytes"] == 16 << 20


def test_roofline_report_annotates_vmem(tmp_path, monkeypatch):
    """--roofline: variants carrying static VMEM estimates are listed
    against the platform budget."""
    monkeypatch.delenv("SWARMDB_VMEM_BYTES", raising=False)
    from swarmdb_tpu.obs.analyze import roofline_report

    dump = {
        "kind": "swarmdb.profile",
        "node": "n0",
        "platform": "tpu",
        "device_kind": "TPU v6 lite",
        "variants": [
            {"variant": "prefill.ragged[w64]", "invocations": 3,
             "device_s": 0.5, "vmem_est_bytes": 8 << 20,
             "vmem_budget_bytes": 32 << 20, "vmem_utilization": 0.25},
            {"variant": "decode[b4]", "invocations": 9, "device_s": 1.0,
             "vmem_est_bytes": 4 << 20},
            {"variant": "other", "invocations": 1, "device_s": 0.1},
        ],
    }
    p = tmp_path / "profile_n0.json"
    p.write_text(json.dumps(dump))
    rep = roofline_report([str(p)])
    entry = rep["dumps"][0]
    assert entry["vmem_budget_bytes"] == 32 << 20
    vm = {v["variant"]: v for v in entry["vmem_variants"]}
    assert vm["prefill.ragged[w64]"]["vmem_utilization"] == 0.25
    # a row missing its own budget falls back to the dump platform's
    assert vm["decode[b4]"]["vmem_budget_bytes"] == 32 << 20
    assert vm["decode[b4]"]["vmem_utilization"] == 0.125
    assert "other" not in vm
