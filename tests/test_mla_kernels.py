"""The two latent (MLA) attention kernels (ops/attention_pallas.py) under
the interpreter against their dense XLA forms (ops/layers.py) at small
widths: rows of different contexts, an empty lane, a page table wider than
any row, a tail block of fewer pages than a block holds; waves of a cold
row, a prefix hit, a one-token rider and a dead row, narrower than a query
block and wider than a key tile."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swarmdb_tpu.ops import attention_pallas as ap
from swarmdb_tpu.ops import layers

H, WD, PS, P, MAXP = 4, 128, 8, 64, 24     # a block of pages: 16 (128 tokens)
F32, BF16 = jnp.float32, jnp.bfloat16


def pool_and_tables(rng, rows, dtype):
    pool = jnp.asarray(rng.normal(size=(P, PS, WD)), F32).astype(dtype)
    ids = rng.permutation(np.arange(1, P))
    tables = np.zeros((rows, MAXP), np.int32)
    n = min(60 // rows, MAXP)
    for r in range(rows):
        tables[r, :n] = ids[r * n:(r + 1) * n]
    return pool, jnp.asarray(tables)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("step", [0, 5])
def test_the_decode_kernel_equals_the_dense_form(dtype, step):
    rng = np.random.default_rng(1)
    B, Kc = 4, 8
    pool, table = pool_and_tables(rng, B, dtype)
    # an empty lane, one page and a bit, exactly two blocks... of 15 pages
    starts = jnp.asarray([0, 11, 120, 37], jnp.int32)
    q = (jnp.asarray(rng.normal(size=(B, H, WD)), F32) * 0.2).astype(dtype)
    chunk = jnp.asarray(rng.normal(size=(B, Kc, WD)), F32).astype(dtype)
    want = layers.latent_decode_attention_reference(
        q, pool, table, chunk, starts, jnp.int32(step))
    got = ap.mla_paged_decode_attention_chunked(
        q, pool, table, chunk, starts, jnp.int32(step), interpret=True)
    assert got.shape == (B, H, WD) and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=1e-5 if dtype == F32 else 2e-2)


WAVES = {
    # (row, prefix_len, new tokens)
    "cold-narrow": (8, [(0, 0, 5)]),
    "hit-and-cold": (32, [(1, 24, 9), (0, 0, 14), (3, 40, 1)]),
    "wider-than-a-tile": (256, [(2, 16, 150), (0, 8, 70), (3, 0, 20)]),
    "rider-only": (16, [(2, 33, 1)]),
}


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", list(WAVES))
def test_the_prefill_kernel_equals_the_dense_form(case, dtype):
    rng = np.random.default_rng(2)
    W, rows = WAVES[case]
    R = 4
    pool, tables = pool_and_tables(rng, R, dtype)
    starts, lens, plens = (np.zeros(R, np.int32) for _ in range(3))
    tok_row = np.full(W, R, np.int32)
    at = 0
    for r, plen, n in rows:
        starts[r], lens[r], plens[r] = at, n, plen
        tok_row[at:at + n] = r
        at += n
    q = (jnp.asarray(rng.normal(size=(W, H, WD)), F32) * 0.2).astype(dtype)
    sfx = jnp.asarray(rng.normal(size=(W, WD)), F32).astype(dtype)
    args = [jnp.asarray(a) for a in (starts, lens, plens)]
    want = layers.latent_prefill_attention_reference(
        q, sfx, pool, tables, *args, jnp.asarray(tok_row))
    got = ap.mla_ragged_prefill_attention(q, sfx, pool, tables, *args,
                                          interpret=True)
    assert got.shape == (W, H, WD) and got.dtype == dtype
    live = tok_row < R
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want, np.float32)[live],
                               atol=1e-5 if dtype == F32 else 2e-2)
    # positions no row owns are zero
    assert not np.asarray(got, np.float32)[~live].any()


def test_the_dispatchers_follow_the_backend(monkeypatch):
    """The kernels on a TPU (or forced, under the interpreter), the dense
    forms elsewhere and where ``SWARMDB_PALLAS=0`` forces them."""
    rng = np.random.default_rng(3)
    pool, table = pool_and_tables(rng, 2, F32)
    q = jnp.zeros((2, H, WD), F32)
    chunk = jnp.zeros((2, 8, WD), F32)
    starts = jnp.asarray([3, 9], jnp.int32)

    def calls():
        # a fresh function a call: a trace is remembered by its function
        return str(jax.make_jaxpr(
            lambda *a: layers.latent_decode_dispatch(*a))(
            q, pool, table, chunk, starts, jnp.int32(0))).count("pallas_call")

    monkeypatch.delenv("SWARMDB_PALLAS", raising=False)
    assert calls() == 0
    monkeypatch.setenv("SWARMDB_PALLAS", "1")
    assert calls() == 1
    monkeypatch.setenv("SWARMDB_PALLAS", "0")
    assert calls() == 0
