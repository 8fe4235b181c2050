"""Pallas TPU kernels: a Mamba-2 decode chunk's read of the slots' state, a
step, and its merge into it, a chunk (``state_read``, ``state_merge``).

A step of a chunk needs, a Mamba-2 layer, ``y0[b] = S_0[layer, b] C[b]``
for every slot ``b`` that holds a sequence: the state as the chunk began
(``models/nemotron_h.py``: frozen for the chunk, as the page pool is)
against this step's ``C``. A slot's ``S`` of a layer is one contiguous
block ``[H P, N]`` of the pool ``[L_m, B, H P, N]`` (1 MB at the published
widths in bf16: 1.3 us of the chip's bandwidth), and most slots are empty
most steps, so ``state_read`` walks the LIVE rows and nothing else, as the
paged decode kernel walks pages (``ops/attention_pallas.py``,
``_paged_chunk_attn_kernel``): the pool stays in HBM where it is, the list
``rows[:n_live]`` (``ops/paged_kv.live_row_list``) and the layer ride as
scalar prefetch, one grid step loops over the list, and a row's block is
copied into one half of a double buffer while the row before it is
multiplied out of the other. Nothing is gathered into a new array first.

The arithmetic is ``nemotron_h.ssm_chunk_step``'s: the state is read in
its stored dtype, ``C`` is float32 and the sum over ``N`` is float32. A
group's rows ``[H P / G, N]`` against its ``C`` [N] is a reduction over
lanes, which is the MXU's work: ``C`` is split into three bfloat16 parts
(``hi + mid + lo`` is the float32 value, 24 bits in three times 8), all
groups' parts are the rows of ONE small left operand ``[3 G, N]``, and
``parts @ S^T`` [3 G, H P] in one bfloat16 pass with float32 accumulation
has every product exact; a row keeps its own group's three sums.

Layout of ``state_read``:
- layer [1], rows [B], n_live [1] int32 in SMEM (scalar prefetch)
- c    [B, G, N] float32   this step's ``C``, resident
- pool [L_m, B, H P, N]    as it is stored (``ANY``: HBM, not copied)
- out  [B, H P] float32    zeros for a slot that is not walked

``state_merge`` is the same walk once a chunk, over (live row, layer):
``S_K = d S_0 + sum_k w_k (x) B_k`` with ``d = exp(cs_K)`` a head and
``w_k = exp(cs_K - cs_k) dt_k x_k``, read and written IN PLACE (the pool is
the output, aliased), a block in and a block out while the block between
them is computed; a slot that is not walked is not touched. A row ``r`` of
the state takes ``w[:, r]``, which the buffers hold along lanes: a group's
``[K, H P / G]`` is transposed on the chip (with ``d`` as one more row, so
it comes out a column), and the sum over the chunk's ``K`` steps is one
MXU pass whose contraction holds the nine products of the two operands'
three bfloat16 parts: every product exact, float32 accumulation, rounded
once where the block is stored.
- rows [B], n_live [1] int32 in SMEM (scalar prefetch)
- d    [L_m, B, H P / 128, 128] float32   ``exp(cs_K)`` a state row
- w    [L_m, B, K, H P] float32
- hB   [L_m, B, K, G N] float32
- pool [L_m, B, H P, N]    in and out, ``ANY``
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
_PART_ROWS = 16     # a bf16 tile's sublanes: the left operand's row multiple
_XROWS = 128        # ``state_merge``'s transposed operand: 9 K parts and ``d``


def takes(pool: jnp.ndarray, groups: int, chunk: int = 0) -> bool:
    """Whether the kernels take this pool, by what the call itself shows:
    a bf16 state whose ``N`` and whose rows a group are lane multiples, a
    chunk (``state_merge``'s) whose nine products a step fit one
    contraction beside ``d``, and a TPU to run on. Everything else
    (float32 pools, the tiny widths, the CPU) keeps ``nemotron_h``'s
    loops over the same list."""
    _, _, HP, N = pool.shape
    return (pool.dtype == jnp.bfloat16 and HP % (groups * LANES) == 0
            and N % LANES == 0 and 9 * chunk < _XROWS and _on_tpu())


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _split3(c):
    """``c`` float32 as three bfloat16 parts whose sum is ``c``."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    hi = c.astype(bf16)
    r = c - hi.astype(f32)
    mid = r.astype(bf16)
    return hi, mid, (r - mid.astype(f32)).astype(bf16)


def _state_read_kernel(layer_ref, rows_ref, nlive_ref, c_ref, pool_hbm,
                       o_ref, buf_ref, sem_ref, *, groups: int):
    layer, n_live = layer_ref[0], nlive_ref[0]
    HP, N = buf_ref.shape[1:]
    per = HP // groups
    n_parts = -(-3 * groups // _PART_ROWS) * _PART_ROWS

    def copy(i, half):
        return pltpu.make_async_copy(pool_hbm.at[layer, rows_ref[i]],
                                     buf_ref.at[half], sem_ref.at[half])

    o_ref[...] = jnp.zeros_like(o_ref)
    # part row ``p`` is group ``p % G``'s; a state row its own group's
    own = (jax.lax.broadcasted_iota(jnp.int32, (n_parts, HP), 0) % groups
           == jax.lax.broadcasted_iota(jnp.int32, (n_parts, HP), 1) // per)

    @pl.when(n_live > 0)
    def _first():
        copy(0, 0).start()

    def row(i, carry):
        half = jax.lax.rem(i, 2)

        @pl.when(i + 1 < n_live)
        def _next():
            copy(i + 1, 1 - half).start()

        copy(i, half).wait()
        b = rows_ref[i]
        parts = jnp.concatenate(
            [*_split3(c_ref[b]),
             jnp.zeros((n_parts - 3 * groups, N), jnp.bfloat16)], axis=0)
        sums = jax.lax.dot_general(
            parts, buf_ref[half], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [n_parts, HP]
        o_ref[pl.ds(b, 1), :] = jnp.sum(jnp.where(own, sums, 0.0), axis=0,
                                        keepdims=True)
        return carry

    jax.lax.fori_loop(0, n_live, row, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def state_read(pool: jnp.ndarray,     # [L_m, B, H P, N] the slots' state
               layer: jnp.ndarray,    # scalar int32
               c: jnp.ndarray,        # [B, G, N] float32
               rows: jnp.ndarray,     # [B] int32, the slots to walk first
               n_live: jnp.ndarray,   # scalar int32: how many are walked
               interpret: bool = False) -> jnp.ndarray:
    """``y0`` [B, H P] float32: ``pool[layer, b] [H P, N]`` times its
    group's ``c[b, g]`` for the first ``n_live`` slots of ``rows``, exact
    zeros for every other slot. Its cost follows ``n_live``: a block of
    the pool a walked row, nothing for the others."""
    _, B, HP, N = pool.shape
    G = c.shape[1]
    whole = lambda *_: (0, 0, 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[pl.BlockSpec((B, G, N), whole),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((B, HP), lambda *_: (0, 0)),
        scratch_shapes=[pltpu.VMEM((2, HP, N), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    as_scalar = lambda a: jnp.reshape(a, (1,)).astype(jnp.int32)
    return pl.pallas_call(
        functools.partial(_state_read_kernel, groups=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, HP), jnp.float32),
        interpret=interpret,
    )(as_scalar(layer), rows.astype(jnp.int32), as_scalar(n_live),
      c.astype(jnp.float32), pool)


def _merge_kernel(rows_ref, nlive_ref, d_hbm, w_hbm, hb_hbm, pool_hbm,
                  out_hbm, s_ref, o_ref, w_ref, hb_ref, d_ref, sem_ref, *,
                  groups: int):
    del pool_hbm                    # the same pool as ``out_hbm``: aliased
    n_live = nlive_ref[0]
    L = w_hbm.shape[0]
    K = w_ref.shape[1]
    HP, N = s_ref.shape[1:]
    per = HP // groups
    total = n_live * L
    f32, bf16 = jnp.float32, jnp.bfloat16
    parts = lambda a: [p.astype(f32) for p in _split3(a)]

    def where(t):
        return jax.lax.rem(t, L), rows_ref[jax.lax.div(t, L)]

    def fetches(t, half):
        l, b = where(t)
        return [pltpu.make_async_copy(src.at[l, b], dst.at[half],
                                      sem_ref.at[i, half])
                for i, (src, dst) in enumerate((
                    (out_hbm, s_ref), (w_hbm, w_ref), (hb_hbm, hb_ref),
                    (d_hbm, d_ref)))]

    def store(t, half):
        l, b = where(t)
        return pltpu.make_async_copy(o_ref.at[half], out_hbm.at[l, b],
                                     sem_ref.at[4, half])

    @pl.when(total > 0)
    def _first():
        for cp in fetches(0, 0):
            cp.start()

    def block(t, carry):
        half = jax.lax.rem(t, 2)

        @pl.when(t + 1 < total)
        def _next():
            for cp in fetches(t + 1, 1 - half):
                cp.start()

        for cp in fetches(t, half):
            cp.wait()

        @pl.when(t >= 2)
        def _stored():
            store(t - 2, half).wait()

        zeros = jnp.zeros((_XROWS - 9 * K, N), f32)
        for g in range(groups):
            cols = pl.ds(g * per, per)
            w3 = parts(w_ref[half, :, cols])               # 3 x [K, per]
            d_row = jnp.concatenate(
                [d_ref[half, pl.ds(g * per // LANES + i, 1), :]
                 for i in range(per // LANES)], axis=1)    # [1, per]
            xt = jnp.concatenate(
                [a for a in w3 for _ in range(3)] + [
                    d_row, jnp.zeros((_XROWS - 9 * K - 1, per), f32)],
                axis=0).T                                  # [per, _XROWS]
            b3 = parts(hb_ref[half, :, pl.ds(g * N, N)])   # 3 x [K, N]
            built = jnp.dot(
                xt.astype(bf16),
                jnp.concatenate(b3 * 3 + [zeros], axis=0).astype(bf16),
                preferred_element_type=f32)                # [per, N]
            decay = xt[:, 9 * K:9 * K + 1]                 # [per, 1]
            o_ref[half, cols, :] = (
                decay * s_ref[half, cols, :].astype(f32) + built
            ).astype(o_ref.dtype)
        store(t, half).start()
        return carry

    jax.lax.fori_loop(0, total, block, 0)
    for back in (2, 1):
        @pl.when(total >= back)
        def _drain(back=back):
            store(total - back, jax.lax.rem(total - back, 2)).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def state_merge(pool: jnp.ndarray,    # [L_m, B, H P, N] the slots' state
                d: jnp.ndarray,       # [L_m, B, H] float32, exp(cs_K)
                w: jnp.ndarray,       # [L_m, B, K, H P] float32
                hB: jnp.ndarray,      # [L_m, B, K, G, N] float32
                rows: jnp.ndarray,    # [B] int32, the slots to walk first
                n_live: jnp.ndarray,  # scalar int32: how many are walked
                interpret: bool = False) -> jnp.ndarray:
    """The pool with ``pool[l, b] <- d[l, b] pool[l, b] + sum_k w[l, b, k]
    (x) hB[l, b, k]`` (``d`` a head, ``hB`` a group) for every layer ``l``
    of the first ``n_live`` slots of ``rows``, float32, rounded once; every
    other slot's blocks are neither read nor written."""
    L, B, HP, N = pool.shape
    K, G = hB.shape[2:4]
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),
        in_specs=[any_space, any_space, any_space, any_space],
        out_specs=any_space,
        scratch_shapes=[pltpu.VMEM((2, HP, N), pool.dtype),
                        pltpu.VMEM((2, HP, N), pool.dtype),
                        pltpu.VMEM((2, K, HP), jnp.float32),
                        pltpu.VMEM((2, K, G * N), jnp.float32),
                        pltpu.VMEM((2, HP // LANES, LANES), jnp.float32),
                        pltpu.SemaphoreType.DMA((5, 2))],
    )
    d_rows = jnp.repeat(d.astype(jnp.float32), HP // d.shape[-1],
                        axis=-1).reshape(L, B, HP // LANES, LANES)
    return pl.pallas_call(
        functools.partial(_merge_kernel, groups=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        input_output_aliases={5: 0},
        interpret=interpret,
    )(rows.astype(jnp.int32), jnp.reshape(n_live, (1,)).astype(jnp.int32),
      d_rows, w.astype(jnp.float32),
      hB.astype(jnp.float32).reshape(L, B, K, G * N), pool)
