"""Pallas TPU kernels: a Mamba-2 decode chunk's read of the slots' state, a
step, and its merge into it, a chunk (``state_read``, ``state_merge``), and
a ragged prefill wave's scan of a layer over its live segments
(``ssm_wave_scan``, below them with its table and its rule).

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


# ----------------------------------------------------- a ragged wave's scan

WAVE_SEGMENT = 128  # tokens a segment: ``nemotron_h.SCAN_CHUNK`` as published
_ALIGN = 8          # a float32 tile's sublanes: what a window starts at
_STEPS = 16         # a segment of so few tokens is walked a token at a time
# the segment table's rows (``wave_segment_table``)
(_AT, _LIVE, _SEED, _SEED_ROW, _SNAP_TO, _SLOT_TO) = range(6)
_CARRY, _ZEROS, _FROM_SNAP, _FROM_SLOT = -1, 0, 1, 2
# the products XLA's ``HIGHEST`` makes of two float32 operands' three
# bfloat16 parts (hi, mid, lo), smallest first
_SIX = ((1, 1), (2, 0), (0, 2), (1, 0), (0, 1), (0, 0))


def takes_wave(pools, stream, groups: int,
               segment: int = WAVE_SEGMENT) -> bool:
    """Whether ``ssm_wave_scan`` takes a wave's scan, by what the call
    itself shows, as ``takes`` (``stream`` is ``dt x``'s shape ``(W, H,
    P)``): bf16 pools of one block shape, ``N`` and
    the rows a group lane multiples, heads that tile a lane row and fit
    one, a stream of whole sublane tiles, the published segment, a TPU.
    Everything else keeps ``nemotron_h.ssm_segments``."""
    W, H, P = stream
    slot, snap = pools
    _, _, HP, N = slot.shape
    return (slot.dtype == snap.dtype == jnp.bfloat16
            and snap.shape[2:] == (HP, N) == (H * P, N)
            and N % LANES == 0 and HP % (groups * LANES) == 0
            and LANES % P == 0 and H <= LANES and H % groups == 0
            and W % _ALIGN == 0 and segment == WAVE_SEGMENT and _on_tpu())


def wave_segment_table(starts, lens, end_lens, src, slots, dst, n_slots: int,
                       width: int):
    """``(table [6, S] int32, n_live)``: a wave's live segments in stream
    order, made once a wave from what ``nemotron_h.ssm_segments`` takes a
    layer (and cut where it cuts: a row at its last page end, each part
    into segments of ``WAVE_SEGMENT`` tokens). A column: where the segment
    starts in the stream, its live tokens, where its state comes from
    (the segment before it, zeros, a snapshot row, a slot row: the last
    three a row's first segment) and which snapshot row (0: none) and
    slot row (-1: none) take the state after it. ``S`` bounds the count
    for a stream of ``width`` tokens; columns past ``n_live`` are dead."""
    Q, i32 = WAVE_SEGMENT, jnp.int32
    R = starts.shape[0]
    lens, starts = lens.astype(i32), starts.astype(i32)
    len1 = jnp.minimum(end_lens, lens).astype(i32)
    n1, n2 = -(-len1 // Q), -(-(lens - len1) // Q)
    upto = jnp.cumsum(n1 + n2)
    s = jnp.arange(-(-width // Q) + 2 * R, dtype=i32)
    r = jnp.minimum(jnp.searchsorted(upto, s, side="right"), R - 1)
    k = s - (upto[r] - n1[r] - n2[r])
    in1 = k < n1[r]
    off = jnp.where(in1, k * Q, len1[r] + (k - n1[r]) * Q)
    live = jnp.minimum(Q, jnp.where(in1, len1[r], lens[r]) - off)
    seed = jnp.where(k > 0, _CARRY, jnp.where(
        src[r] > 0, _FROM_SNAP, jnp.where(src[r] < 0, _FROM_SLOT, _ZEROS)))
    seed_row = jnp.where(src[r] > 0, src[r],
                         jnp.clip(slots[r], 0, n_slots - 1))
    to = jnp.where(end_lens > 0, dst, 0)
    snap_to = jnp.where(in1 & (k == n1[r] - 1), to[r], 0)
    # a row that ends AT its last page end has no second part
    slot_to = jnp.where((k == n1[r] + n2[r] - 1) & (slots[r] < n_slots),
                        slots[r], -1)
    table = jnp.stack([starts[r] + off, live, seed, seed_row, snap_to,
                       slot_to]).astype(i32)
    return table, upto[-1].astype(i32)


def _dot6(a3, b3):
    """``a @ b`` of two float32 operands given as their three bfloat16
    parts: the six products of ``HIGHEST``, float32 accumulation."""
    acc = None
    for i, j in _SIX:
        t = jnp.dot(a3[i], b3[j], preferred_element_type=jnp.float32)
        acc = t if acc is None else acc + t
    return acc


def _wave_kernel(layer_ref, nseg_ref, tab_ref, xbc_hbm, dt_hbm, la_hbm,
                 slot_in, snap_in, y_ref, slot_hbm, snap_hbm, x_buf,
                 dt_buf, la_buf, b_buf, c_buf, seed_buf, out_buf, st_ref,
                 xdw_ref, col_ref, row_ref, pend_ref, sem_ref, seed_sem,
                 out_sem, *, heads: int):
    del slot_in, snap_in            # the same pools as the outputs: aliased
    layer, n_seg = layer_ref[0], nseg_ref[0]
    f32, bf16 = jnp.float32, jnp.bfloat16
    TW, HP = x_buf.shape[1:]
    T = TW - _ALIGN
    SW = _STEPS + _ALIGN
    Wp = y_ref.shape[0]
    G, N, PG = st_ref.shape          # PG: a group's lanes of the state
    per, P = heads // G, HP // heads
    tiles, nh = PG // LANES, LANES // P       # lane rows a group, heads each
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def by_head(vals):
        """``vals[q]`` (broadcastable to a lane row) for the lanes of the
        lane row's head ``q``."""
        out = vals[-1]
        for q in range(nh - 2, -1, -1):
            out = jnp.where(lane < (q + 1) * P, vals[q], out)
        return out

    def window(s, n):
        """Where the ``n`` stream rows start that hold segment ``s`` from
        its tile's first row on, and the segment's first row among them."""
        at = tab_ref[_AT, s]
        a = jnp.minimum(at // _ALIGN * _ALIGN, Wp - n)
        return pl.multiple_of(a, _ALIGN), at - a

    def fetches(s, half, act):
        """Start or wait for (``act``) segment ``s``'s windows into half
        ``half``: ``T + 8`` rows, or ``_STEPS + 8`` for a segment that is
        walked a token at a time."""
        short = tab_ref[_LIVE, s] <= _STEPS
        for when, n in ((short, SW), (jnp.logical_not(short), TW)):
            @pl.when(when)
            def _(n=n):
                a, _ = window(s, n)
                GN = b_buf.shape[2]
                # ``x | B | C`` out of the conv's row as it is stored
                for i, (src, lo, dst) in enumerate((
                        (xbc_hbm, 0, x_buf), (xbc_hbm, HP, b_buf),
                        (xbc_hbm, HP + GN, c_buf), (dt_hbm, 0, dt_buf),
                        (la_hbm, 0, la_buf))):
                    act(pltpu.make_async_copy(
                        src.at[pl.ds(a, n), pl.ds(lo, dst.shape[2])],
                        dst.at[half, pl.ds(0, n)], sem_ref.at[i, half]))

    def seed_copy(s, act):
        kind, at_row = tab_ref[_SEED, s], tab_ref[_SEED_ROW, s]
        for which, pool in ((_FROM_SNAP, snap_hbm), (_FROM_SLOT, slot_hbm)):
            @pl.when(kind == which)
            def _(pool=pool):
                act(pltpu.make_async_copy(pool.at[layer, at_row], seed_buf,
                                          seed_sem.at[0]))

    def stored():
        # both stores leave ``out_buf``: wait before it is written again
        for i, pool in enumerate((snap_hbm, slot_hbm)):
            @pl.when(pend_ref[i] == 1)
            def _(i=i, pool=pool):
                pltpu.make_async_copy(out_buf, pool.at[layer, 0],
                                      out_sem.at[i]).wait()
                pend_ref[i] = 0

    def turned(x, off, n):
        """The window's rows from ``off`` on, first: a segment starts at
        any token, a copy at a tile's first row."""
        rows_n = x.shape[0]
        return pltpu.roll(x, jax.lax.rem(rows_n - off, rows_n), 0)[:n]

    def place(y, a, off, live, lanes_t):
        """``y``'s first ``live`` rows to the stream's rows from ``a +
        off`` on, the rows around them left as they are."""
        n = y.shape[0] + _ALIGN
        rows_w = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)
        y_w = pltpu.roll(jnp.concatenate(
            [y, jnp.zeros((_ALIGN, LANES), f32)], axis=0), off, 0)
        at_y = (pl.ds(a, n), lanes_t)
        y_ref[at_y] = jnp.where((rows_w >= off) & (rows_w < off + live),
                                y_w, y_ref[at_y])

    def head_columns(vals, n):
        """``col_ref[g, i, :n]`` <- ``vals[i]`` [n, 128 heads] with group
        ``g``'s heads in the first lanes: a head's column is then a
        static slice whatever the group."""
        for g in range(G):
            back = (LANES - g * per) % LANES
            for i, v in enumerate(vals):
                col_ref[g, i, :n] = pltpu.roll(v, back, 1) if back else v

    def dual(s, half):
        """A segment in ``nemotron_h._segment``'s dual form."""
        a, off = window(s, TW)
        live = tab_ref[_LIVE, s]
        rows = jax.lax.broadcasted_iota(jnp.int32, (T, 1), 0)
        causal = (jax.lax.broadcasted_iota(jnp.int32, (T, T), 0)
                  >= jax.lax.broadcasted_iota(jnp.int32, (T, T), 1))
        mask = rows < live
        cs = jnp.where(mask, turned(la_buf[half], off, T), 0.0)  # [T, 128]
        sh = 1
        while sh < T:        # the running sum down the rows, log T passes
            cs = cs + jnp.where(rows >= sh, pltpu.roll(cs, sh, 0), 0.0)
            sh *= 2
        row_ref[...] = cs.T                                  # [128, T]
        head_columns((cs, jnp.exp(cs), jnp.exp(cs[T - 1:T, :] - cs),
                      turned(dt_buf[half], off, T)), T)

        def group(g, c):
            lanes_g = pl.ds(pl.multiple_of(g * N, LANES), N)
            B3 = _split3(turned(b_buf[half, :, lanes_g], off, T).T)  # [N, T]
            C3 = _split3(turned(c_buf[half, :, lanes_g], off, T))    # [T, N]
            scores = _dot6(C3, B3)                               # [j, i]
            S = st_ref[g]                                        # [N, PG]
            S3 = _split3(S)
            col = lambda what, h: col_ref[g, what, :, h:h + 1]   # [T, 1]
            d_rows = []
            for t in range(tiles):
                lanes_t = pl.ds(pl.multiple_of(g * PG + t * LANES, LANES),
                                LANES)
                heads_t = range(t * nh, (t + 1) * nh)
                # ``dt x``, a head's ``dt`` over its lanes       [T, 128]
                x = jnp.where(mask, turned(x_buf[half, :, lanes_t], off, T),
                              0.0) * by_head([col(3, h) for h in heads_t])
                x3 = _split3(x)
                ys = []
                for h in heads_t:
                    # exp of a masked difference, never a masked exp
                    diff = col(0, h) - row_ref[pl.ds(g * per + h, 1), :]
                    m = jnp.exp(jnp.where(causal, diff, -jnp.inf)) * scores
                    ys.append(_dot6(_split3(m), x3))
                from_state = _dot6(C3, [p[:, t * LANES:(t + 1) * LANES]
                                        for p in S3])            # [T, 128]
                place(by_head(ys) + by_head([col(1, h) for h in heads_t])
                      * from_state, a, off, live, lanes_t)
                xdw_ref[:, t * LANES:(t + 1) * LANES] = x * by_head(
                    [col(2, h) for h in heads_t])
                d_rows.append(jnp.broadcast_to(by_head(
                    [col_ref[g, 1, T - 1:T, h:h + 1] for h in heads_t]),
                    (1, LANES)))
            built = _dot6(B3, _split3(xdw_ref[...]))             # [N, PG]
            st_ref[g] = jnp.concatenate(d_rows, axis=1) * S + built
            return c

        jax.lax.fori_loop(0, G, group, 0)

    def steps(s, half):
        """A segment of up to ``_STEPS`` tokens (the part behind a row's
        last page end, a one-token row) a token at a time: ``S <- exp(dt
        a) S + dt x (x) B``, ``y = S C``, float32 on the vector unit, whose
        cost follows the tokens and not a tile."""
        a, off = window(s, SW)
        live = tab_ref[_LIVE, s]
        head_columns((jnp.exp(turned(la_buf[half, :SW], off, _STEPS)),
                      turned(dt_buf[half, :SW], off, _STEPS)), _STEPS)
        tall = lambda v: jnp.concatenate(
            [v, jnp.zeros((LANES - _STEPS, v.shape[1]), f32)], axis=0)

        def group(g, c):
            lanes_g = pl.ds(pl.multiple_of(g * N, LANES), N)
            # a token's ``B`` and ``C`` as columns: [N, token]
            BT = tall(turned(b_buf[half, :SW, lanes_g], off, _STEPS)).T
            CT = tall(turned(c_buf[half, :SW, lanes_g], off, _STEPS)).T
            lanes = [pl.ds(pl.multiple_of(g * PG + t * LANES, LANES), LANES)
                     for t in range(tiles)]
            xs = [turned(x_buf[half, :SW, lanes_t], off, _STEPS)
                  for lanes_t in lanes]                          # [16, 128]
            for k in range(_STEPS):
                @pl.when(k < live)
                def _(k=k):
                    b, c_ = BT[:, k:k + 1], CT[:, k:k + 1]       # [N, 1]
                    for t in range(tiles):
                        at_t = (g, slice(None),
                                slice(t * LANES, (t + 1) * LANES))
                        d, dt = (by_head([col_ref[g, i, k:k + 1, h:h + 1]
                                          for h in range(t * nh,
                                                         (t + 1) * nh)])
                                 for i in (0, 1))
                        S = d * st_ref[at_t] + b * (dt * xs[t][k:k + 1, :])
                        st_ref[at_t] = S
                        # ``y``'s row, kept where ``dual`` keeps ``dt x``
                        xdw_ref[k:k + 1, t * LANES:(t + 1) * LANES] = (
                            jnp.sum(S * c_, axis=0, keepdims=True))
            for t, lanes_t in enumerate(lanes):
                place(xdw_ref[:_STEPS, t * LANES:(t + 1) * LANES], a, off,
                      live, lanes_t)
            return c

        jax.lax.fori_loop(0, G, group, 0)

    pend_ref[0] = 0
    pend_ref[1] = 0
    y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(n_seg > 0)
    def _first():
        fetches(0, 0, lambda cp: cp.start())
        seed_copy(0, lambda cp: cp.start())

    def segment(s, carry):
        half = jax.lax.rem(s, 2)
        live = tab_ref[_LIVE, s]
        kind = tab_ref[_SEED, s]
        fetches(s, half, lambda cp: cp.wait())

        @pl.when(kind == _ZEROS)
        def _zeros():
            st_ref[...] = jnp.zeros_like(st_ref)

        @pl.when(kind > _ZEROS)
        def _seeded():
            seed_copy(s, lambda cp: cp.wait())

            def group(g, c):
                r0 = pl.multiple_of(g * PG, PG)
                st_ref[g] = seed_buf[pl.ds(r0, PG), :].astype(f32).T
                return c

            jax.lax.fori_loop(0, G, group, 0)

        @pl.when(s + 1 < n_seg)
        def _next():
            fetches(s + 1, 1 - half, lambda cp: cp.start())
            seed_copy(s + 1, lambda cp: cp.start())

        @pl.when(live > _STEPS)
        def _dual():
            dual(s, half)

        @pl.when(live <= _STEPS)
        def _steps():
            steps(s, half)

        snap_to, slot_to = tab_ref[_SNAP_TO, s], tab_ref[_SLOT_TO, s]

        @pl.when((snap_to > 0) | (slot_to >= 0))
        def _store():
            stored()

            def group(g, c):
                r0 = pl.multiple_of(g * PG, PG)
                # rounded once, where it is stored
                out_buf[pl.ds(r0, PG), :] = st_ref[g].T.astype(bf16)
                return c

            jax.lax.fori_loop(0, G, group, 0)

            @pl.when(snap_to > 0)
            def _part_end():
                pltpu.make_async_copy(out_buf, snap_hbm.at[layer, snap_to],
                                      out_sem.at[0]).start()
                pend_ref[0] = 1

            @pl.when(slot_to >= 0)
            def _row_end():
                pltpu.make_async_copy(out_buf, slot_hbm.at[layer, slot_to],
                                      out_sem.at[1]).start()
                pend_ref[1] = 1

        return carry

    jax.lax.fori_loop(0, n_seg, segment, 0)
    stored()


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_wave_scan(xbc: jnp.ndarray,      # [W, H P + 2 G N] f32: x | B | C
                  dt: jnp.ndarray,       # [W, H] float32
                  la: jnp.ndarray,       # [W, H] float32, ``dt a``
                  table: jnp.ndarray,    # [6, S] int32 (wave_segment_table)
                  n_live: jnp.ndarray,   # scalar int32: its live columns
                  layer: jnp.ndarray,    # scalar int32
                  slot: jnp.ndarray,     # [L_m, B, H P, N] the slots' state
                  snap: jnp.ndarray,     # [L_m, 1 + S, H P, N] snapshots
                  interpret: bool = False):
    """A ragged wave's Mamba-2 scan of one layer: ``nemotron_h.
    ssm_segments``' walk over the wave's live segments as one call, with
    its arithmetic (float32 operands, the dual form a segment, ``exp`` of
    the masked difference, every dot the six bfloat16 products of
    ``HIGHEST`` with float32 accumulation, the seed read in its stored
    dtype and the state rounded once where it is stored). Returns ``(y
    [W, H P] float32, slot, snap)``: ``y`` exact zeros where no live
    segment lies, the pools IN PLACE. ``xbc`` is the conv's output as
    the layer has it, a token's ``x`` (the heads side by side, as in
    ``y``), ``B`` and ``C`` (the groups side by side) in one row: the
    windows are cut out of it where it lies, and ``dt x`` is made here, a
    head's ``dt`` over its lanes. Left to XLA the three slices and that
    product were four passes over the stream a layer, one of them a copy
    into another layout (PERF.md section 6, PR 53).

    The pools stay in HBM (``ANY``, aliased in and out) and are touched
    only where the table says: a row's seed block is read once, at its
    first segment, from one source; the state is written once at a
    part's end and once at the row's end; between a row's segments it
    stays in VMEM, float32, a group's block transposed (``[N, rows]``: the
    state's rows are the lanes of ``y`` and of ``dt x``, so no operand of
    a segment's dots is transposed but ``B``). A segment's operands are a
    window of ``segment + 8`` stream rows from the tile its first token
    lies in, turned in VMEM so that the token is row 0; the next
    segment's windows, and a next row's seed, are fetched while this one
    is computed. A segment of up to ``_STEPS`` tokens (the part behind a
    row's last page end, a one-token row) is walked a token at a time in
    float32 on the vector unit, from a window of ``_STEPS + 8`` rows: its
    cost follows its tokens, not a tile (13 us a one-token row a layer
    with its seed in and its state out, ``scripts/race_ssm_wave.py``).
    A row's seed must not be a block an EARLIER row of the
    same wave writes (the engine never plans one: ``Engine.
    _take_snapshots``): it may be fetched before that row's store.

    ``y`` is one VMEM block for the whole stream (16 KB a token)."""
    W, H = la.shape
    HP, N = slot.shape[2:]
    GN = (xbc.shape[1] - HP) // 2
    G = GN // N
    T = WAVE_SEGMENT
    TW = T + _ALIGN
    Wp = max(W, TW)
    f32 = jnp.float32
    fit = lambda a, lanes: jnp.pad(
        a.astype(f32).reshape(W, -1),
        ((0, Wp - W), (0, lanes - a.size // W)))
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[any_space, any_space, any_space, any_space, any_space],
        out_specs=[pl.BlockSpec((Wp, HP), lambda *_: (0, 0)), any_space,
                   any_space],
        scratch_shapes=[pltpu.VMEM((2, TW, HP), f32),
                        pltpu.VMEM((2, TW, LANES), f32),
                        pltpu.VMEM((2, TW, LANES), f32),
                        pltpu.VMEM((2, TW, GN), f32),
                        pltpu.VMEM((2, TW, GN), f32),
                        pltpu.VMEM((HP, N), slot.dtype),
                        pltpu.VMEM((HP, N), slot.dtype),
                        pltpu.VMEM((G, N, HP // G), f32),
                        pltpu.VMEM((T, HP // G), f32),
                        pltpu.VMEM((G, 4, T, LANES), f32),
                        pltpu.VMEM((LANES, T), f32),
                        pltpu.SMEM((2,), jnp.int32),
                        pltpu.SemaphoreType.DMA((5, 2)),
                        pltpu.SemaphoreType.DMA((1,)),
                        pltpu.SemaphoreType.DMA((2,))],
    )
    as_scalar = lambda a: jnp.reshape(a, (1,)).astype(jnp.int32)
    y, slot, snap = pl.pallas_call(
        functools.partial(_wave_kernel, heads=H),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Wp, HP), f32),
                   jax.ShapeDtypeStruct(slot.shape, slot.dtype),
                   jax.ShapeDtypeStruct(snap.shape, snap.dtype)],
        input_output_aliases={6: 1, 7: 2},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_wave_vmem_bytes(Wp, HP, GN)),
        interpret=interpret,
    )(as_scalar(layer), as_scalar(n_live), table.astype(jnp.int32),
      fit(xbc, HP + 2 * GN), fit(dt, LANES), fit(la, LANES), slot, snap)
    return y[:W], slot, snap


def _wave_vmem_bytes(Wp: int, HP: int, GN: int) -> int:
    """What ``ssm_wave_scan`` asks of VMEM: the stream's ``y`` (the
    pipeline keeps two), the double-buffered windows, the state three
    times (float32, a seed, a block to store) and as much again for the
    values between them."""
    TW = WAVE_SEGMENT + _ALIGN
    held = (2 * Wp * HP + 2 * TW * (HP + 2 * GN + 2 * LANES)) * 4 + 8 * HP * LANES
    return held + (24 << 20)
