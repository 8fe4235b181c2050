#!/usr/bin/env python
"""Seeded kernel-crime drill — the kernel sanitizer's NEGATIVE test.

The CI ``kerncheck`` job runs the kernel/ragged suites under
``SWARMDB_KERNCHECK=1`` and fails on any violation; this script is the
other direction: it deliberately commits every kernel crime the shadow
interpreter hunts — an out-of-bounds page id in a wave's write
descriptors (SWL901-class), a sabotaged kernel that loses one row's
finalize so the canary survives (SWL905-class), and an unmasked
finalize whose grid rows race on the shared output block
(SWL902-class) — and exits non-zero unless the detector FIRED on each
and dumped evidence to disk. A green kerncheck run only means
something if this drill stays red-on-crime.

Run: SWARMDB_KERNCHECK=1 python scripts/kerncheck_drill.py
(the script forces the flag itself so a bare invocation also works).
"""

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

os.environ.setdefault("SWARMDB_KERNCHECK", "1")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("SWARMDB_NODE_ID", "kerncheck-drill")


def main() -> int:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    from jax.experimental import pallas as pl

    from swarmdb_tpu.obs import kerncheck
    from swarmdb_tpu.ops import attention_pallas as ap

    dump_dir = os.environ.get("SWARMDB_FLIGHT_DIR")
    if not dump_dir:
        dump_dir = tempfile.mkdtemp(prefix="kerncheck-drill-")
        os.environ["SWARMDB_FLIGHT_DIR"] = dump_dir

    if not kerncheck.enabled():
        print("FAIL: SWARMDB_KERNCHECK=1 did not enable the sanitizer")
        return 1

    rng = np.random.default_rng(0)
    (q, sk, sv, kp, vp, tables, starts, lens, plens,
     _tok_row) = kerncheck._random_ragged_case(rng)
    ps = np.asarray(kp).shape[1]
    P = np.asarray(kp).shape[0]
    maxp = np.asarray(tables).shape[1]
    base = kerncheck.ragged_prefill_body(kp, tables)

    # -- crime 1: OOB page id in the wave's write descriptors ---------
    bad_tables = np.array(np.asarray(tables), copy=True)
    live_r = int(np.nonzero(np.asarray(lens) > 0)[0][0])
    bad_tables[live_r, 0] = P + 7                 # points past the pool
    kerncheck.check_wave_descriptors(
        np.array([live_r], np.int32),
        np.array([0], np.int32), bad_tables, P, ps)

    # -- crime 2: short write (one live row's finalize lost) ----------
    def short_write(*refs):
        # grid (query block, row): whatever row ``live_r``'s step wrote
        # into the output block (refs[9]) is taken back
        o_ref = refs[9]
        before = o_ref[...]
        base(*refs)
        if pl.program_id(1) == live_r:
            o_ref[...] = before

    kerncheck.shadow_ragged_prefill(
        q, sk, sv, kp, vp, tables, starts, lens, plens,
        kernel=short_write)

    # -- crime 3: block race (unmasked finalize, varying values) ------
    def unmasked(*refs):
        base(*refs)
        o_ref = refs[9]
        o_ref[...] = (np.zeros(o_ref.shape, np.float32)
                      + 1.5 * (pl.program_id(1) + 1))

    kerncheck.shadow_ragged_prefill(
        q, sk, sv, kp, vp, tables, starts, lens, plens,
        kernel=unmasked)

    # -- crime 4: wrong scale on the int8 pool ------------------------
    # quantize the same pools, then hand the quant kernel DOUBLED
    # K-scales while the XLA reference dequantizes with the true scales —
    # the parity check must catch the scale-bookkeeping divergence
    from swarmdb_tpu.ops.layers import ragged_prefill_attention_reference
    from swarmdb_tpu.ops.paged_kv import QuantPool, _quantize_pages

    # draw until some live row ATTENDS prefix pages (plens > 0) — a
    # suffix-only wave never reads the pool, so wrong scales are moot
    while not ((np.asarray(plens) > 0) & (np.asarray(lens) > 0)).any():
        (q, sk, sv, kp, vp, tables, starts, lens, plens,
         _tok_row) = kerncheck._random_ragged_case(rng)
    kq, ks = _quantize_pages(kp)
    vq, vs = _quantize_pages(vp)
    import jax.numpy as jnp

    got = np.asarray(ap.ragged_paged_prefill_attention_quant(
        q, sk, sv, kq, ks * 2.0, vq, vs, tables, starts, lens, plens,
        interpret=True))
    want_q = np.asarray(ragged_prefill_attention_reference(
        q, sk, sv, QuantPool(kq, ks), QuantPool(vq, vs), tables,
        starts, lens, plens, jnp.asarray(_tok_row)))
    live = np.asarray(_tok_row) < np.asarray(tables).shape[0]
    err = float(np.max(np.abs(got[live] - want_q[live])))
    tol = kerncheck.parity_tol("int8")
    kerncheck.registry().note_check("drill.wrong-scale")
    if err > tol:
        kerncheck.registry().record(
            "parity", "ragged_paged_prefill_attention_quant",
            f"seeded wrong-scale crime: doubled K scales shift live "
            f"outputs by {err:.3e} (> {tol}) vs the true-scale "
            f"reference — scale bookkeeping divergence detected",
            {"max_err": err})

    kinds = {v["kind"] for v in kerncheck.registry().violations()}
    want = {"oob-block", "short-write", "write-race", "parity"}
    missing = want - kinds
    dump = os.path.join(dump_dir, "kerncheck_kerncheck-drill.json")
    print(f"violations recorded: {sorted(kinds)}")
    print(f"dump: {dump} exists={os.path.exists(dump)}")
    if missing:
        print(f"FAIL: detector did not fire for: {sorted(missing)}")
        return 1
    if not os.path.exists(dump):
        print("FAIL: violation dump never landed on disk")
        return 1
    print("OK: every seeded kernel crime was detected and dumped")
    return 0


if __name__ == "__main__":
    sys.exit(main())
