"""Pallas TPU kernels: decode-step GQA attention (dense slot cache and
ragged block-paged cache) and ragged paged prefill attention.

The serving hot path (engine decode chunks) issues attention with ONE query
per slot against that slot's cache lane. The XLA einsum path materializes
fp32 scores [B, Hq, S] in HBM between ops; these kernels keep the scores
in VMEM: per-kv-head MXU dots for q·K, masked softmax in registers, one
dot against V — the only HBM traffic is the cache itself, which is the
unavoidable read.

The paged engine's default decode kernel is
`paged_decode_gqa_attention_chunked`: one grid step a group of 32 slots,
the pools left in HBM, and inside the step a loop over the slots that hold
a sequence (a list the forward makes once a step) and, a row, a loop that
copies its LIVE pages, a block of pages a trip, into a double buffer — so
a call costs what the live rows' contexts hold, not what the batch or the
page table could hold. Its int8 twin still walks a `(B, maxp + 1)` grid,
one page a step.
`ragged_paged_prefill_attention` walks the same way: one grid step a
(query block, wave row), and inside it the row's live prefix pages and
its suffix tiles, a 128-token block a trip; its int8 twin keeps the grid
of the table's width.

Dense whole-lane kernel, layout (grid = (B,)):
- q block   [1, Hq, D]      — all query heads of the slot
- k/v block [1, S, Hkv, D]  — the slot's full cache lane, all kv heads
- lengths   [B] in SMEM     — valid prefix length (= q position + 1)

Single-chip path only: under tensor parallelism the cache's head axis is
sharded and this call would force a gather; the engine enables the kernel
when the model is unsharded (see ops/layers.gqa_attention dispatch).

No reference counterpart (the reference has no model code, SURVEY §5.7);
design per /opt/skills/guides/pallas_guide.md and the ragged-paged-attention
pattern noted in PAPERS.md.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

_SMEM = pltpu.SMEM


def _decode_attn_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *,
                        n_kv_heads: int):
    # q_ref [1, Hq, D]; k_ref/v_ref [1, S, Hkv, D]; len_ref [B] (SMEM,
    # whole array — TPU requires rank-1 blocks be full or 128-multiples,
    # so the kernel indexes its row by grid position instead of slicing).
    # One grid cell = one slot, ALL heads: per-kv-head blocks would need a
    # [1, G, D] tile with G < 8, below the TPU sublane minimum.
    Hq, D = q_ref.shape[1], q_ref.shape[2]
    Hkv = n_kv_heads
    G = Hq // Hkv
    q = q_ref[0].reshape(Hkv, G, D).astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)                   # [S, Hkv, D]
    v = v_ref[0].astype(jnp.float32)
    S = k.shape[0]
    scale = 1.0 / (D**0.5)

    length = len_ref[pl.program_id(0)]
    valid = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1) < length

    # static unroll over kv heads: Mosaic's dot_general needs batch dims in
    # matching positions, so a batched [Hkv, ...] einsum won't lower; Hkv
    # is small (8 for the Llama-3 family) and the unrolled dots pipeline
    outs = []
    for h in range(Hkv):
        scores = jax.lax.dot_general(
            q[h], k[:, h, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                      # [G, S]
        scores = jnp.where(valid, scores, -1e30)
        m = jnp.max(scores, axis=-1, keepdims=True)
        p = jnp.exp(scores - m)
        denom = jnp.sum(p, axis=-1, keepdims=True)
        out = jax.lax.dot_general(
            p, v[:, h, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) / denom                                      # [G, D]
        outs.append(out)
    o_ref[0] = jnp.concatenate(outs, axis=0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_gqa_attention(
    q: jnp.ndarray,        # [B, Hq, D] (single decode query per slot)
    cache_k: jnp.ndarray,  # [B, S, Hkv, D]
    cache_v: jnp.ndarray,  # [B, S, Hkv, D]
    lengths: jnp.ndarray,  # [B] int32 — valid prefix per slot (pos + 1)
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns [B, Hq, D] in q.dtype. ``interpret=True`` runs the kernel on
    CPU for tests (pallas interpreter)."""
    B, Hq, D = q.shape
    S, Hkv = cache_k.shape[1], cache_k.shape[2]
    # the kernel holds a slot's WHOLE K and V lanes in VMEM: two
    # double-buffered input blocks plus their f32 copies. Refuse here, at
    # trace time, what the chip's compiler refuses after seconds of
    # work (v5e, 8 kv heads x 128: S=512 compiles, S=1024 is 16.32 MB
    # against the 16 MiB scoped limit).
    from ..analysis.kernelcheck import vmem_budget

    lanes = S * Hkv * D * (4 * cache_k.dtype.itemsize + 8)
    if lanes >= vmem_budget():
        raise ValueError(
            f"decode_gqa_attention keeps whole KV lanes in VMEM: S={S} x "
            f"{Hkv} kv heads x {D} needs {lanes} bytes, the limit is "
            f"{vmem_budget()}; use the chunked or paged kernel or unset "
            f"SWARMDB_PALLAS")

    grid = (B,)
    return pl.pallas_call(
        functools.partial(_decode_attn_kernel, n_kv_heads=Hkv),
        grid=grid,
        in_specs=[
            pl.BlockSpec((B,), lambda b: (0,), memory_space=_SMEM),
            pl.BlockSpec((1, Hq, D), lambda b: (b, 0, 0)),
            pl.BlockSpec((1, S, Hkv, D), lambda b: (b, 0, 0, 0)),
            pl.BlockSpec((1, S, Hkv, D), lambda b: (b, 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), lambda b: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        interpret=interpret,
    )(lengths, q, cache_k, cache_v)


# ---------------------------------------------------------------------------
# Ragged PAGED decode attention (ops/paged_kv.py pool layout).
#
# The page TABLE and the per-slot lengths ride as scalar-prefetch operands
# (PrefetchScalarGridSpec, SMEM). A page is read across ALL kv heads
# ([ps, Hkv, D] — the Hkv axis may not be sliced: Mosaic requires the last
# two block dims be (8, 128)-divisible or whole, and a (…, 1, D) per-head
# block violates the sublane rule) and a static unroll over the Hkv heads
# runs the online softmax per head, exactly like the dense kernel above.
# Scores and softmax state stay in VMEM scratch across the pages (online
# softmax), so nothing but the output tile is written back. Two ways of
# walking a row's pages live here:
#
#   * `_paged_chunk_attn_kernel` (the chunked decode path, what the engine
#     runs): grid (ceil(B / 32),), one step a group of `_ROW_GROUP` slots,
#     whose queries, chunk buffers and outputs are whole VMEM blocks
#     indexed by a row read from SMEM. The step walks the LIVE rows: the
#     list ``rows[:n_live]`` rides as scalar prefetch beside the table
#     (`ops.paged_kv.live_row_list` makes it once a decode step from the
#     un-offset table: a slot whose table row is all trash holds no
#     sequence; the kernel cannot tell from its own table, which the
#     layer scan hands over offset by l * P). A slot that is not walked
#     costs nothing and reads exact zeros. The pools are operands in ANY
#     space (HBM, exactly as `pools_flat` hands them over: [L*P, ps, Hkv,
#     D] — no copy, no layout change) and a row loops over its live
#     pages in blocks of `_pages_per_block` pages: ceil(live pages /
#     block) trips, read from the prefetched ``starts``. Each trip's
#     pages are copied by the kernel itself (`make_async_copy`, one
#     contiguous page a DMA, DMA semaphores) into one half of a double
#     buffer while the other half is computed on, and folded into the
#     softmax as ONE [block * ps]-token tile; the double buffer runs
#     across rows (a row's last trip starts the next row's first block).
#     A row with an empty prefix makes no trip and starts no DMA; pages
#     past a row's last live one are never fetched. The cost of a call
#     follows the live rows and their contexts (v5e, PERF.md section 6,
#     PR 47: 4-6 us a call whatever it holds, 1.2 us a live row, 1.7-1.8
#     us a block of 128 tokens).
#   * the int8 twin `_paged_chunk_attn_kernel_quant` (further down): grid
#     (B, maxp + 1) with the page axis innermost, one page a grid step
#     through a BlockSpec whose index_map picks the physical page, the
#     chunk buffer the last step. Dead iterations (j beyond the slot's
#     live pages) remap to the SAME page as the last live step, and
#     Pallas skips the DMA for a block whose indices didn't change — HBM
#     traffic is ~live pages, but every dead step is still a grid step
#     (about 0.18 us on v5e: 0.7 ms a call at a 256-page table whatever
#     the rows hold, PERF.md section 6, PR 30).


def _online_update(h, s, v, acc_ref, m_ref, l_ref):
    """Fold one masked score tile ``s`` [G, Tk] + value tile ``v`` [Tk, D]
    into head ``h``'s running online-softmax state (flash-attention
    rescaling)."""
    m_prev = m_ref[h][:, :1]                           # [G, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)                    # rescale old state
    p = jnp.exp(s - m_new)                             # [G, Tk]
    l_new = l_ref[h][:, :1] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_ref[h] = jnp.broadcast_to(m_new, m_ref[h].shape)
    l_ref[h] = jnp.broadcast_to(l_new, l_ref[h].shape)


def _attend_tile(q_ref, k_tile_ref, v_tile_ref, valid, n_kv_heads,
                 acc_ref, m_ref, l_ref, k_scale=None, v_scale=None):
    """One [Tk]-token KV tile against every head's query: per-kv-head MXU
    dots (a batched einsum won't lower in Mosaic) folded into the online
    softmax scratch. ``valid`` is the [1, Tk] position mask.

    ``k_scale``/``v_scale`` ([Hkv] f32, or None) are the quantized-pool
    page scales: int8 tiles are dequantized HERE, in VMEM, after the
    page's one HBM read — the roofline sees half the bytes and the MXU
    still runs the f32 math (SWARMDB_KV_DTYPE=int8, ISSUE 18)."""
    Hq, D = q_ref.shape[1], q_ref.shape[2]
    G = Hq // n_kv_heads
    q = q_ref[0].reshape(n_kv_heads, G, D).astype(jnp.float32)
    k = k_tile_ref[0].astype(jnp.float32)              # [Tk, Hkv, D]
    v = v_tile_ref[0].astype(jnp.float32)
    if k_scale is not None:
        k = k * k_scale.reshape(1, n_kv_heads, 1)
    if v_scale is not None:
        v = v * v_scale.reshape(1, n_kv_heads, 1)
    scale = 1.0 / (D ** 0.5)
    for h in range(n_kv_heads):
        s = jax.lax.dot_general(
            q[h], k[:, h, :], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                      # [G, Tk]
        _online_update(h, jnp.where(valid, s, -1e30), v[:, h, :],
                       acc_ref, m_ref, l_ref)


# VMEM the chunked decode kernel's page loop may hold for one block of
# pages: both pools' double buffers and the f32 working copies of the
# block being computed on. `_pages_per_block` sizes a block under it.
_PAGE_BLOCK_VMEM_BYTES = 4 * 1024 * 1024
# tokens a block: one lane width of scores a head. On v5e at 32/8 heads
# of 128, page 16 (PERF.md, PR 30): 5 live rows of 300-1,000 tokens in
# 16 take 0.079 ms a call at 128, 0.097 at 256, 0.102 at 64; 16 rows of
# 4,088 tokens 0.90 / 1.11 / 1.35 ms
_PAGE_BLOCK_TOKENS = 128


def _pages_per_block(page_size: int, n_kv_heads: int, head_dim: int,
                     itemsize: int, maxp: int) -> int:
    """Pages one trip of the chunked decode kernel's page loop takes: a
    block of `_PAGE_BLOCK_TOKENS` tokens (one lane width of scores a
    head), fewer where a block's buffers would pass
    `_PAGE_BLOCK_VMEM_BYTES`, never less than one page nor more than the
    table holds. From shapes alone: nothing else chooses it."""
    per_token = n_kv_heads * head_dim * (4 * itemsize + 8)
    tokens = min(_PAGE_BLOCK_TOKENS, _PAGE_BLOCK_VMEM_BYTES // per_token)
    return max(1, min(tokens // page_size, maxp))


# slots one grid step of the chunked decode kernel holds: ``q``, the chunk
# buffers and the output come in as blocks of this many rows (at 32/8
# heads of 128 in bf16: 256 KB + 2 x 512 KB + 256 KB, beside the 1 MB page
# double buffer), so a batch up to it is ONE grid step
_ROW_GROUP = 32


def _paged_chunk_attn_kernel(table_ref, start_ref, step_ref, rows_ref,
                             nlive_ref, q_ref, k_hbm, v_hbm, ck_ref, cv_ref,
                             o_ref, kbuf_ref, vbuf_ref, sem_ref, acc_ref,
                             m_ref, l_ref, *, page_size: int,
                             n_kv_heads: int, pages_per_block: int,
                             n_groups: int, window):
    """Ragged paged attention + in-chunk segment under ONE online softmax.

    Grid (groups,): one step a group of ``_ROW_GROUP`` slots, whose
    ``q``, chunk buffers and output are whole VMEM blocks. The step
    zeroes its output block and walks the LIVE rows of its group: entries
    ``rows[lo:hi]`` of the scalar-prefetched list (live slots first, in
    slot order; ``n_live`` of them), so a slot that holds no sequence
    costs nothing and reads exact zeros. A row's FROZEN prefix (valid
    strictly below the chunk start) is walked in blocks of
    ``pages_per_block`` pages, ``ceil(live pages / pages_per_block)``
    trips read from the prefetched ``starts``. A trip waits for its
    block's page DMAs (pool -> one half of the double buffer, one
    contiguous page a copy, ids from the prefetched table), starts the
    next block's into the other half and folds its
    ``pages_per_block * page_size`` tokens into the online softmax; a
    row's last trip starts the NEXT row's first block instead, so only
    the group's first row waits for a copy nothing hides. Pages past a
    row's last live one are never fetched. Then the row's [Kc] chunk
    buffer (entries 0..step) and its finalize.
    """
    g = pl.program_id(0)
    step = step_ref[0]
    n_live = nlive_ref[0]
    Bg, Hq, D = q_ref.shape
    Hkv = n_kv_heads
    ps, ppb = page_size, pages_per_block
    tile = ppb * ps
    maxp = table_ref.shape[1]

    if n_groups == 1:
        lo, hi = 0, n_live
    else:
        # the list is in slot order: the group's rows are one run of it
        def below(bound):
            return jax.lax.fori_loop(
                0, n_live,
                lambda i, c: c + jnp.where(rows_ref[i] < bound, 1, 0),
                jnp.int32(0))

        lo, hi = below(g * Bg), below((g + 1) * Bg)

    def walk_of(i):
        """(slot, frozen prefix length, live pages) of list entry ``i``;
        no pages past the group's last row."""
        b = rows_ref[jnp.minimum(i, rows_ref.shape[0] - 1)]
        start = start_ref[b]
        # truncating lax.div on non-negative numerators (`_last_live_page`)
        pages = jnp.minimum(
            jax.lax.div(jax.lax.max(start, 0) + (ps - 1), jnp.int32(ps)),
            maxp)
        return b, start, jnp.where(i < hi, pages, 0)

    def page_copies(b, blk, slot, i):
        dst = pl.ds(i * ps, ps)
        pid = table_ref[b, blk * ppb + i]
        return (pltpu.make_async_copy(k_hbm.at[pid], kbuf_ref.at[slot, dst],
                                      sem_ref.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[pid], vbuf_ref.at[slot, dst],
                                      sem_ref.at[1, slot]))

    def fetch(b, live_pages, blk, slot):
        for i in range(ppb):
            live = blk * ppb + i < live_pages

            @pl.when(live)
            def _start():
                for cp in page_copies(b, blk, slot, i):
                    cp.start()

            @pl.when(jnp.logical_not(live))
            def _blank():
                # a page of the tail block that is not fetched: its keys
                # are masked, its values must still be finite (0 * NaN)
                vbuf_ref[slot, pl.ds(i * ps, ps)] = jnp.zeros(
                    (ps,) + vbuf_ref.shape[2:], vbuf_ref.dtype)

    def wait(b, live_pages, blk, slot):
        for i in range(ppb):
            @pl.when(blk * ppb + i < live_pages)
            def _wait():
                for cp in page_copies(b, blk, slot, i):
                    cp.wait()

    o_ref[...] = jnp.zeros_like(o_ref)
    b0, _, pages0 = walk_of(lo)

    @pl.when(pages0 > 0)
    def _first():
        fetch(b0, pages0, 0, 0)

    def row(i, slot0):
        # ``slot0``: the half this row's first block is in (or would be)
        b, start, live_pages = walk_of(i)
        nb, _, nxt_pages = walk_of(i + 1)
        n_blocks = jax.lax.div(live_pages + (ppb - 1), jnp.int32(ppb))
        r = b - g * Bg
        q_row = q_ref.at[pl.ds(r, 1)]

        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

        @pl.when((n_blocks == 0) & (nxt_pages > 0))
        def _next_row():
            fetch(nb, nxt_pages, 0, slot0)

        def block(blk, carry):
            slot = jax.lax.rem(slot0 + blk, 2)
            mine = blk + 1 < n_blocks

            @pl.when(mine | (nxt_pages > 0))
            def _next():
                fetch(jnp.where(mine, b, nb),
                      jnp.where(mine, live_pages, nxt_pages),
                      jnp.where(mine, blk + 1, 0), 1 - slot)

            wait(b, live_pages, blk, slot)
            pos = blk * tile + jax.lax.broadcasted_iota(
                jnp.int32, (1, tile), 1)
            valid = pos < start
            if window is not None:
                valid &= pos > (start + step - window)
            _attend_tile(q_row, kbuf_ref.at[pl.ds(slot, 1)],
                         vbuf_ref.at[pl.ds(slot, 1)], valid, Hkv, acc_ref,
                         m_ref, l_ref)
            return carry

        jax.lax.fori_loop(0, n_blocks, block, 0)

        Kc = ck_ref.shape[1]
        idx = jax.lax.broadcasted_iota(jnp.int32, (1, Kc), 1)
        valid = idx <= step
        if window is not None:
            valid &= (start + idx) > (start + step - window)
        _attend_tile(q_row, ck_ref.at[pl.ds(r, 1)], cv_ref.at[pl.ds(r, 1)],
                     valid, Hkv, acc_ref, m_ref, l_ref)

        denom = jnp.maximum(l_ref[:, :, :1], 1e-30)
        o_ref[r] = (acc_ref[...] / denom).reshape(Hq, D).astype(o_ref.dtype)
        return jax.lax.rem(slot0 + n_blocks, 2)

    jax.lax.fori_loop(lo, hi, row, jnp.int32(0))


def _last_live_page(n, ps):
    # (n - 1) // ps for n >= 1, clamped to 0 — via truncating lax.div on a
    # guaranteed-nonnegative numerator: jnp's floor ``//`` expands into a
    # sign/rem jaxpr that bloats the scalar-core index_map program
    return jax.lax.div(jax.lax.max(n - 1, 0), jnp.int32(ps))


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_gqa_attention_chunked(
    q: jnp.ndarray,           # [B, Hq, D] one decode query per slot
    k_pages: jnp.ndarray,     # [P, ps, Hkv, D] FROZEN pool (or flat [L*P, ..])
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, maxp] int32
    chunk_k: jnp.ndarray,     # [B, Kc, Hkv, D] chunk buffer
    chunk_v: jnp.ndarray,
    starts: jnp.ndarray,      # [B] int32 frozen prefix length (chunk start)
    step: jnp.ndarray,        # scalar int32 current step within the chunk
    rows: jnp.ndarray,        # [B] int32 the slots to walk, in slot order
    n_live: jnp.ndarray,      # scalar int32: how many of ``rows`` are walked
    window=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Two-segment ragged paged decode attention; returns [B, Hq, D],
    exact zeros for a slot that is not among the first ``n_live`` of
    ``rows``. The pools stay in HBM as they are handed over; the kernel
    copies each walked row's live pages itself, so its cost follows
    ``n_live`` and ``starts``, not the batch or the table's width."""
    B, Hq, D = q.shape
    _, ps, Hkv, _ = k_pages.shape
    maxp = page_table.shape[1]
    G = Hq // Hkv
    Kc = chunk_k.shape[1]
    ppb = _pages_per_block(ps, Hkv, D, k_pages.dtype.itemsize, maxp)
    Bg = min(B, _ROW_GROUP)
    n_groups = -(-B // Bg)

    def q_map(g, *prefetched):
        return (g, 0, 0)

    def chunk_map(g, *prefetched):
        return (g, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(n_groups,),
        in_specs=[
            pl.BlockSpec((Bg, Hq, D), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((Bg, Kc, Hkv, D), chunk_map),
            pl.BlockSpec((Bg, Kc, Hkv, D), chunk_map),
        ],
        out_specs=pl.BlockSpec((Bg, Hq, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, ppb * ps, Hkv, D), k_pages.dtype),  # K halves
            pltpu.VMEM((2, ppb * ps, Hkv, D), v_pages.dtype),  # V halves
            pltpu.SemaphoreType.DMA((2, 2)),         # [pool, half]
            pltpu.VMEM((Hkv, G, D), jnp.float32),    # acc
            pltpu.VMEM((Hkv, G, 128), jnp.float32),  # running max (bcast)
            pltpu.VMEM((Hkv, G, 128), jnp.float32),  # running denom (bcast)
        ],
    )
    return pl.pallas_call(
        functools.partial(_paged_chunk_attn_kernel, page_size=ps,
                          n_kv_heads=Hkv, pages_per_block=ppb,
                          n_groups=n_groups, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), starts.astype(jnp.int32),
      jnp.reshape(step, (1,)).astype(jnp.int32), rows.astype(jnp.int32),
      jnp.reshape(n_live, (1,)).astype(jnp.int32), q, k_pages, v_pages,
      chunk_k, chunk_v)


# ---------------------------------------------------------------------------
# Ragged paged PREFILL attention (ISSUE 11 tentpole; the walk of ISSUE 32).
#
# One packed token STREAM per admission wave: the engine concatenates the
# wave's rows back to back (no per-row bucket padding) and describes them
# with per-row ``(start, len, prefix_len)`` descriptors that ride as
# scalar-prefetch operands (SMEM). Grid (nQ, R): the stream is cut into nQ
# query blocks of ``tile`` tokens and grid row ``r`` is wave row r. A row
# with no token in the query block (a dead row, a row of another block)
# is one empty grid step: no DMA, no compute. A row that meets the block
# walks ITS OWN keys inside the step, a 128-token block a trip:
#
#   * its PREFIX, ``ceil(prefix_len / (pages_per_block * ps))`` trips read
#     from the prefetched ``prefix_lens`` (a fresh row makes none). The
#     pools are operands in ANY space (HBM, exactly as `pools_flat` hands
#     them over: [L*P, ps, Hkv, D] with the table already offset by
#     l * P). A trip's live pages are copied by the kernel itself
#     (`make_async_copy`, one contiguous page a DMA, ids from the
#     prefetched table) into one half of a double buffer while the other
#     half is folded; pages past the row's last live one are not fetched;
#   * then its SUFFIX, the packed K/V stream (ANY space too) in
#     [tile]-token tiles from the row's first tile to the query block's
#     own (causality: later keys are masked for every query in it),
#     through the same double buffer.
#
# Every trip folds into one online softmax (`_online_update`, the same
# machinery the decode kernels use); each (block, row) pair keeps its own
# state and ends in a masked finalize of the row's lanes. Causality inside
# the stream is POSITIONAL: rows are contiguous, so "key index <= query
# index within the same row" is exactly causal order and no per-token
# position array is needed in the kernel. So a call costs what the wave
# holds: R grid steps a query block, and a trip for every 128 keys a
# row's queries can see. (The int8 twin `_ragged_prefill_kernel_quant`
# still walks a grid (nQ, R, maxp + nQ), one 16-token page a step: 4,112
# steps a layer for a chat wave's ~20 live ones, 1.2 ms a call whatever
# it holds; PERF.md section 6, PR 32.)
#
# VMEM holds ONE query block (q, out, fp32 accumulators for all heads) and
# the two halves of one key block, so the footprint is that of a
# ``tile``-token wave whatever the stream width: at Llama-3-8B heads
# (32 q / 8 kv, head_dim 128) a 128-token block is what fits v5e's default
# 16 MiB scoped-VMEM limit (whole-stream residency was refused by the
# chip's compiler from W=256 up).


def _ragged_row_meets_block(q0, n_q, start, ln):
    """Whether stream row [start, start+ln) has a token in the query
    block [q0, q0+n_q)."""
    return (ln > 0) & (start < q0 + n_q) & (start + ln > q0)


def _ragged_suffix_tile(t, q0, n_q, start, ln, tile):
    """(tile index, whether it is live) for suffix step ``t`` of a row
    seen from the query block at ``q0``: tiles run from the row's first
    to its last, cut at the block's own (causality — later keys are
    masked for every query in it). Steps past the last, and the page
    steps before the first (t < 0), re-point at a live tile, so their
    DMA is skipped. Truncating ``lax.div`` on non-negative numerators,
    as in `_last_live_page`."""
    first = jax.lax.div(start, jnp.int32(tile))
    last = jax.lax.div(
        jax.lax.max(jnp.minimum(start + ln, q0 + n_q) - 1, 0),
        jnp.int32(tile))
    step = first + jax.lax.max(t, 0)
    return jnp.minimum(step, last), step <= last


def _ragged_fold(q_ref, k, v, valid, n_kv_heads, acc_ref, m_ref, l_ref):
    """Fold one KV tile (k/v [Tk, Hkv, D] f32, valid [Wq*G, Tk] or
    [1, Tk]) into the query block's online-softmax state; score rows are
    (w, g) pairs, w-major — matching q.reshape(Wq, Hkv, G, D)."""
    Wq, Hq, D = q_ref.shape
    G = Hq // n_kv_heads
    scale = 1.0 / (D ** 0.5)
    q = q_ref[...].reshape(Wq, n_kv_heads, G, D).astype(jnp.float32)
    for h in range(n_kv_heads):
        s = jax.lax.dot_general(
            q[:, h].reshape(Wq * G, D), k[:, h, :],
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                      # [Wq*G, Tk]
        _online_update(h, jnp.where(valid, s, -1e30), v[:, h, :],
                       acc_ref, m_ref, l_ref)


def _ragged_prefill_kernel(table_ref, starts_ref, lens_ref, plens_ref,
                           q_ref, sk_hbm, sv_hbm, kp_hbm, vp_hbm, o_ref,
                           kbuf_ref, vbuf_ref, sem_ref, acc_ref, m_ref,
                           l_ref, *, page_size: int, n_kv_heads: int,
                           pages_per_block: int, window):
    """Grid (nQ, R): query block ``qb`` against wave row ``r``. A row
    that meets the block walks its live prefix pages in blocks of
    ``pages_per_block`` and then its suffix tiles up to the block's own,
    every trip through one double buffer: start the next trip's copies
    into the other half, wait for this trip's, fold. A row that does not
    meet the block does nothing."""
    qb = pl.program_id(0)
    r = pl.program_id(1)
    Wq, Hq, D = q_ref.shape
    Hkv = n_kv_heads
    G = Hq // Hkv
    ps, ppb = page_size, pages_per_block
    blk = ppb * ps
    T = kbuf_ref.shape[1]             # keys a trip: max(blk, Wq)
    far = 1 << 30
    maxp = table_ref.shape[1]
    start = starts_ref[r]
    ln = lens_ref[r]
    plen = plens_ref[r]
    q0 = qb * Wq

    @pl.when(r == 0)
    def _zero_out():
        # the query block's output is revisited by every grid row (index
        # map constant in r) and finalized with a masked write per row —
        # positions no row owns (stream padding) stay zero. The value
        # buffer starts finite: a key that is not fetched is masked, its
        # weight is 0, and 0 * NaN is not
        o_ref[...] = jnp.zeros_like(o_ref)
        vbuf_ref[...] = jnp.zeros_like(vbuf_ref)

    @pl.when(_ragged_row_meets_block(q0, Wq, start, ln))
    def _row():
        # truncating lax.div on non-negative numerators (`_last_live_page`)
        live_pages = jnp.minimum(
            jax.lax.div(jax.lax.max(plen, 0) + (ps - 1), jnp.int32(ps)),
            maxp)
        n_pref = jax.lax.div(live_pages + (ppb - 1), jnp.int32(ppb))
        # suffix tiles: the row's first to the block's own
        first = jax.lax.div(start, jnp.int32(Wq))
        n_trips = n_pref + (qb - first + 1)

        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

        def copies(t, slot, act):
            """Start or wait for (``act``) trip ``t``'s keys into half
            ``slot``: the live pages of prefix block ``t``, or suffix
            tile ``t - n_pref`` of the row."""
            @pl.when(t < n_pref)
            def _pages():
                def page(i, carry):
                    pid = table_ref[r, t * ppb + i]
                    dst = pl.ds(i * ps, ps)
                    act(pltpu.make_async_copy(
                        kp_hbm.at[pid], kbuf_ref.at[slot, dst],
                        sem_ref.at[0, slot]))
                    act(pltpu.make_async_copy(
                        vp_hbm.at[pid], vbuf_ref.at[slot, dst],
                        sem_ref.at[1, slot]))
                    return carry

                jax.lax.fori_loop(
                    0, jnp.minimum(live_pages - t * ppb, ppb), page, 0)

            @pl.when(t >= n_pref)
            def _tile():
                src = pl.ds((first + t - n_pref) * Wq, Wq)
                dst = pl.ds(0, Wq)
                act(pltpu.make_async_copy(
                    sk_hbm.at[src], kbuf_ref.at[slot, dst],
                    sem_ref.at[0, slot]))
                act(pltpu.make_async_copy(
                    sv_hbm.at[src], vbuf_ref.at[slot, dst],
                    sem_ref.at[1, slot]))

        # stream index of each score row, and key index within a trip
        wq = q0 + jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (Wq * G, 1), 0),
            jnp.int32(G))
        kidx = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)

        copies(0, 0, lambda cp: cp.start())

        def trip(t, carry):
            slot = jax.lax.rem(t, 2)

            @pl.when(t + 1 < n_trips)
            def _next():
                copies(t + 1, 1 - slot, lambda cp: cp.start())

            copies(t, slot, lambda cp: cp.wait())
            # one mask for both kinds of trip, its bounds chosen by
            # scalar selects. A prefix trip's keys are positions
            # t*blk + i of the row, valid below ``plen``; a suffix
            # trip's are stream indices of its tile, valid inside the
            # row and not after the query (``far`` lifts that bound off
            # a prefix trip: every cached key precedes every query)
            pre = t < n_pref
            kx = kidx + jnp.where(pre, t * blk, (first + t - n_pref) * Wq)
            valid = ((kx >= jnp.where(pre, 0, start))
                     & (kx < jnp.where(pre, plen, start + ln))
                     & (kx <= wq + jnp.where(pre, far, 0)))
            if window is not None:
                # query w sits at plen + w - start in row r
                valid &= kx > wq - window + jnp.where(pre, plen - start, 0)
            if blk < T or Wq < T:
                valid &= kidx < jnp.where(pre, blk, Wq)
            _ragged_fold(q_ref, kbuf_ref[slot].astype(jnp.float32),
                         vbuf_ref[slot].astype(jnp.float32), valid, Hkv,
                         acc_ref, m_ref, l_ref)
            return carry

        jax.lax.fori_loop(0, n_trips, trip, 0)

        denom = jnp.maximum(l_ref[:, :, :1], 1e-30)    # [Hkv, Wq*G, 1]
        out = (acc_ref[...] / denom).reshape(Hkv, Wq, G, D)
        out = out.transpose(1, 0, 2, 3).reshape(Wq, Hq, D)
        w_iota = q0 + jax.lax.broadcasted_iota(jnp.int32, (Wq, 1, 1), 0)
        mine = (w_iota >= start) & (w_iota < start + ln)
        o_ref[...] = jnp.where(mine, out.astype(o_ref.dtype), o_ref[...])


def _pad_stream(tile, *streams):
    """Zero-pad packed [W, H, D] streams along W to whole blocks: a
    multiple of ``tile``, or of the 8-row sublane quantum for a stream
    shorter than one tile. Returns the padded streams."""
    n = streams[0].shape[0]
    blk = min(tile, -(-n // 8) * 8)
    pad = (-n) % blk
    if not pad:
        return streams
    return tuple(jnp.pad(s, ((0, pad), (0, 0), (0, 0))) for s in streams)


@functools.partial(jax.jit, static_argnames=("window", "tile", "interpret"))
def ragged_paged_prefill_attention(
    q: jnp.ndarray,           # [W, Hq, D] packed query stream
    sfx_k: jnp.ndarray,       # [W, Hkv, D] packed suffix K (this wave's)
    sfx_v: jnp.ndarray,
    k_pages: jnp.ndarray,     # [P, ps, Hkv, D] single-layer page pool
    v_pages: jnp.ndarray,     #   (or the flat [L*P, ..] one)
    row_tables: jnp.ndarray,  # [R, maxp] int32 page ids per wave row
    starts: jnp.ndarray,      # [R] int32 — row r's offset in the stream
    lens: jnp.ndarray,        # [R] int32 — row r's token count (0 = dead)
    prefix_lens: jnp.ndarray,  # [R] int32 — tokens already in r's pages
    window=None,
    tile: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Ragged paged prefill attention over a packed wave; returns
    [W, Hq, D] in q.dtype (positions outside every row are zero).
    ``tile`` is both the query block and the suffix K/V tile. The pools
    and the suffix stream stay in HBM as they are handed over; the
    kernel copies each row's live pages and suffix tiles itself, so its
    cost follows the descriptors, not the table's width."""
    n_tok = q.shape[0]
    q, sfx_k, sfx_v = _pad_stream(tile, q, sfx_k, sfx_v)
    W, Hq, D = q.shape
    _, ps, Hkv, _ = k_pages.shape
    R, maxp = row_tables.shape
    G = Hq // Hkv
    Tq = min(tile, W)         # W is a whole number of blocks
    n_st = W // Tq
    ppb = _pages_per_block(ps, Hkv, D, k_pages.dtype.itemsize, maxp)
    keys = max(ppb * ps, Tq)  # a trip: a block of pages or a suffix tile
    table = row_tables.astype(jnp.int32)
    starts = starts.astype(jnp.int32)
    lens = lens.astype(jnp.int32)
    plens = prefix_lens.astype(jnp.int32)
    # the pools' dtype is the suffix's (the model casts before attention)
    sfx_k = sfx_k.astype(k_pages.dtype)
    sfx_v = sfx_v.astype(v_pages.dtype)

    def q_map(qb, r, table_ref, starts_ref, lens_ref, plens_ref):
        return (qb, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_st, R),
        in_specs=[
            pl.BlockSpec((Tq, Hq, D), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        # swarmlint: revisit[r] -- every row step of a query block
        # writes into its one resident output block; the masked finalize
        # at the end of a row's walk writes each row's lanes exactly once
        out_specs=pl.BlockSpec((Tq, Hq, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, keys, Hkv, D), k_pages.dtype),    # K halves
            pltpu.VMEM((2, keys, Hkv, D), v_pages.dtype),    # V halves
            pltpu.SemaphoreType.DMA((2, 2)),              # [pool, half]
            pltpu.VMEM((Hkv, Tq * G, D), jnp.float32),    # acc
            pltpu.VMEM((Hkv, Tq * G, 128), jnp.float32),  # running max
            pltpu.VMEM((Hkv, Tq * G, 128), jnp.float32),  # running denom
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_prefill_kernel, page_size=ps,
                          n_kv_heads=Hkv, pages_per_block=ppb,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((W, Hq, D), q.dtype),
        interpret=interpret,
    )(table, starts, lens, plens, q, sfx_k, sfx_v, k_pages, v_pages)
    return out[:n_tok]


def _dense_chunk_attn_kernel(start_ref, step_ref, q_ref, k_ref, v_ref,
                             ck_ref, cv_ref, o_ref, acc_ref, m_ref, l_ref,
                             *, tile: int, n_kv_heads: int, window):
    """Dense two-segment decode attention (the serve-bench hot path):
    stream the FROZEN slot cache in [tile]-token blocks, then fold the
    in-chunk buffer, all under one online softmax. Mirrors
    `_paged_chunk_attn_kernel_quant`'s grid with the page table replaced
    by the slot's own contiguous lane; dead tiles (>= the slot's chunk
    start) re-point at the last live tile so their DMA is skipped — HBM
    traffic scales with each slot's LIVE prefix, which the XLA einsum
    path (always a full [S] read + materialized fp32 scores) cannot do.
    """
    b = pl.program_id(0)
    j = pl.program_id(1)
    n_tiles = pl.num_programs(1) - 1
    start = start_ref[b]              # frozen prefix length = chunk start
    step = step_ref[0]
    Hkv = n_kv_heads

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((j < n_tiles) & (j * tile < start))
    def _cache():
        pos = j * tile + jax.lax.broadcasted_iota(
            jnp.int32, (1, tile), 1)
        valid = pos < start
        if window is not None:
            valid &= pos > (start + step - window)
        _attend_tile(q_ref, k_ref, v_ref, valid, Hkv, acc_ref, m_ref, l_ref)

    @pl.when(j == n_tiles)
    def _chunk():
        Kc = ck_ref.shape[1]
        idx = jax.lax.broadcasted_iota(jnp.int32, (1, Kc), 1)
        valid = idx <= step
        if window is not None:
            valid &= (start + idx) > (start + step - window)
        _attend_tile(q_ref, ck_ref, cv_ref, valid, Hkv, acc_ref, m_ref,
                     l_ref)

        denom = jnp.maximum(l_ref[:, :, :1], 1e-30)
        Hq, D = q_ref.shape[1], q_ref.shape[2]
        o_ref[0] = (acc_ref[...] / denom).reshape(Hq, D).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "tile", "interpret"))
def decode_gqa_attention_chunked(
    q: jnp.ndarray,          # [B, Hq, D] one decode query per slot
    cache_k: jnp.ndarray,    # [B, S, Hkv, D] FROZEN slot cache
    cache_v: jnp.ndarray,
    chunk_k: jnp.ndarray,    # [B, Kc, Hkv, D] this chunk's K so far
    chunk_v: jnp.ndarray,
    starts: jnp.ndarray,     # [B] int32 frozen prefix length (chunk start)
    step: jnp.ndarray,       # scalar int32 current step within the chunk
    window=None,
    tile: int = 256,
    interpret: bool = False,
) -> jnp.ndarray:
    """Dense two-segment decode attention; returns [B, Hq, D] in q.dtype.
    Requires S % tile == 0 (the dispatch in ops/layers.py checks)."""
    B, Hq, D = q.shape
    S, Hkv = cache_k.shape[1], cache_k.shape[2]
    G = Hq // Hkv
    n_tiles = S // tile
    starts = starts.astype(jnp.int32)
    step_arr = jnp.reshape(step, (1,)).astype(jnp.int32)

    def q_map(b, j, start_ref, step_ref):
        return (b, 0, 0)

    def kv_map(b, j, start_ref, step_ref):
        last_live = _last_live_page(start_ref[b], tile)
        return (b, jnp.minimum(j, last_live), 0, 0)

    def chunk_map(b, j, start_ref, step_ref):
        return (b, 0, 0, 0)

    def o_map(b, j, start_ref, step_ref):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_tiles + 1),
        in_specs=[
            pl.BlockSpec((1, Hq, D), q_map),
            pl.BlockSpec((1, tile, Hkv, D), kv_map),
            pl.BlockSpec((1, tile, Hkv, D), kv_map),
            pl.BlockSpec((1, chunk_k.shape[1], Hkv, D), chunk_map),
            pl.BlockSpec((1, chunk_k.shape[1], Hkv, D), chunk_map),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), o_map),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, D), jnp.float32),    # acc
            pltpu.VMEM((Hkv, G, 128), jnp.float32),  # running max (bcast)
            pltpu.VMEM((Hkv, G, 128), jnp.float32),  # running denom (bcast)
        ],
    )
    out = pl.pallas_call(
        functools.partial(_dense_chunk_attn_kernel, tile=tile,
                          n_kv_heads=Hkv, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        interpret=interpret,
    )(starts, step_arr, q, cache_k, cache_v, chunk_k, chunk_v)
    return out


# ---------------------------------------------------------------------------
# Quantized-pool kernel variants (SWARMDB_KV_DTYPE=int8, ISSUE 18).
#
# The grid form of the paged kernels (page axis in the grid, DMA-skip
# index maps: `(B, maxp + 1)` for the chunked decode twin with the chunk
# segment as its last step), the same online softmax as the kernels
# above — the ONLY difference is the KV operands: int8 page
# payloads plus a per-page-per-head f32 scale operand shaped [P, 1, Hkv]
# (block (1, 1, Hkv), whole in its last two dims — Mosaic-legal — and
# indexed by the SAME page map as the payload, so a page's scale row
# rides the page's DMA step). Dequantization happens inside
# `_attend_tile` in VMEM: HBM sees half the bytes, the MXU still runs
# f32. Suffix streams and in-chunk buffers stay full precision — only
# what lives in the POOL is quantized.


def _paged_chunk_attn_kernel_quant(table_ref, start_ref, step_ref, q_ref,
                                   k_ref, ks_ref, v_ref, vs_ref, ck_ref,
                                   cv_ref, o_ref, acc_ref, m_ref, l_ref,
                                   *, page_size: int, n_kv_heads: int,
                                   window):
    """Quantized two-segment decode: int8 pages dequantize per tile, the
    in-chunk buffer (never pool-resident) stays full precision."""
    b = pl.program_id(0)
    j = pl.program_id(1)
    maxp = pl.num_programs(1) - 1
    start = start_ref[b]
    step = step_ref[0]
    Hq, D = q_ref.shape[1], q_ref.shape[2]
    Hkv = n_kv_heads

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when((j < maxp) & (j * page_size < start))
    def _pages():
        pos = j * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        valid = pos < start
        if window is not None:
            valid &= pos > (start + step - window)
        _attend_tile(q_ref, k_ref, v_ref, valid, Hkv, acc_ref, m_ref,
                     l_ref, k_scale=ks_ref[...], v_scale=vs_ref[...])

    @pl.when(j == maxp)
    def _chunk():
        Kc = ck_ref.shape[1]
        idx = jax.lax.broadcasted_iota(jnp.int32, (1, Kc), 1)
        valid = idx <= step
        if window is not None:
            valid &= (start + idx) > (start + step - window)
        _attend_tile(q_ref, ck_ref, cv_ref, valid, Hkv, acc_ref, m_ref,
                     l_ref)

        denom = jnp.maximum(l_ref[:, :, :1], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).reshape(Hq, D).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_gqa_attention_chunked_quant(
    q: jnp.ndarray,           # [B, Hq, D]
    k_pages: jnp.ndarray,     # [P, ps, Hkv, D] int8 FROZEN pool
    k_scale: jnp.ndarray,     # [P, Hkv] f32
    v_pages: jnp.ndarray,
    v_scale: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, maxp] int32
    chunk_k: jnp.ndarray,     # [B, Kc, Hkv, D] full-precision chunk buffer
    chunk_v: jnp.ndarray,
    starts: jnp.ndarray,      # [B] int32 frozen prefix length
    step: jnp.ndarray,        # scalar int32 step within the chunk
    window=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Quantized two-segment ragged paged decode; returns [B, Hq, D]."""
    B, Hq, D = q.shape
    P, ps, Hkv, _ = k_pages.shape
    maxp = page_table.shape[1]
    G = Hq // Hkv
    table = page_table.astype(jnp.int32)
    starts = starts.astype(jnp.int32)
    step_arr = jnp.reshape(step, (1,)).astype(jnp.int32)
    ks3 = k_scale.reshape(P, 1, Hkv)
    vs3 = v_scale.reshape(P, 1, Hkv)

    def q_map(b, j, table_ref, start_ref, step_ref):
        return (b, 0, 0)

    def kv_map(b, j, table_ref, start_ref, step_ref):
        last_live = _last_live_page(start_ref[b], ps)
        return (table_ref[b, jnp.minimum(j, last_live)], 0, 0, 0)

    def sc_map(b, j, table_ref, start_ref, step_ref):
        last_live = _last_live_page(start_ref[b], ps)
        return (table_ref[b, jnp.minimum(j, last_live)], 0, 0)

    def chunk_map(b, j, table_ref, start_ref, step_ref):
        return (b, 0, 0, 0)

    def o_map(b, j, table_ref, start_ref, step_ref):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, maxp + 1),
        in_specs=[
            pl.BlockSpec((1, Hq, D), q_map),
            pl.BlockSpec((1, ps, Hkv, D), kv_map),
            pl.BlockSpec((1, 1, Hkv), sc_map),
            pl.BlockSpec((1, ps, Hkv, D), kv_map),
            pl.BlockSpec((1, 1, Hkv), sc_map),
            pl.BlockSpec((1, chunk_k.shape[1], Hkv, D), chunk_map),
            pl.BlockSpec((1, chunk_k.shape[1], Hkv, D), chunk_map),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), o_map),
        scratch_shapes=[
            pltpu.VMEM((Hkv, G, D), jnp.float32),    # acc
            pltpu.VMEM((Hkv, G, 128), jnp.float32),  # running max (bcast)
            pltpu.VMEM((Hkv, G, 128), jnp.float32),  # running denom (bcast)
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_chunk_attn_kernel_quant, page_size=ps,
                          n_kv_heads=Hkv, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        interpret=interpret,
    )(table, starts, step_arr, q, k_pages, ks3, v_pages, vs3,
      chunk_k, chunk_v)
    return out


def _ragged_prefill_kernel_quant(table_ref, starts_ref, lens_ref,
                                 plens_ref, q_ref, sk_ref, sv_ref, kp_ref,
                                 kps_ref, vp_ref, vps_ref, o_ref, acc_ref,
                                 m_ref, l_ref, *, page_size: int,
                                 n_kv_heads: int, n_pages: int, window):
    """Quantized ragged prefill: int8 PREFIX pages dequantize per page
    tile; the packed suffix stream (this wave's own K/V, not yet
    pool-resident) stays full precision. Same grid, blocks and masks as
    `_ragged_prefill_kernel`."""
    qb = pl.program_id(0)
    r = pl.program_id(1)
    j = pl.program_id(2)
    n_steps = pl.num_programs(2)
    Wq, Hq, D = q_ref.shape
    tile = sk_ref.shape[0]
    Hkv = n_kv_heads
    G = Hq // Hkv
    ps = page_size
    start = starts_ref[r]
    ln = lens_ref[r]
    plen = plens_ref[r]
    q0 = qb * Wq
    meets = _ragged_row_meets_block(q0, Wq, start, ln)

    @pl.when((r == 0) & (j == 0))
    def _zero_out():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(meets & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)

    wq = q0 + jax.lax.div(
        jax.lax.broadcasted_iota(jnp.int32, (Wq * G, 1), 0), jnp.int32(G))
    q_abs = plen + wq - start

    @pl.when(meets & (j < n_pages) & (j * ps < plen))
    def _prefix():
        kpos = j * ps + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        valid = kpos < plen
        if window is not None:
            valid &= kpos > (q_abs - window)
        kd = kp_ref[0].astype(jnp.float32) * kps_ref[...].reshape(1, Hkv, 1)
        vd = vp_ref[0].astype(jnp.float32) * vps_ref[...].reshape(1, Hkv, 1)
        _ragged_fold(q_ref, kd, vd, jnp.broadcast_to(valid, (Wq * G, ps)),
                     Hkv, acc_ref, m_ref, l_ref)

    @pl.when(meets & (j >= n_pages))
    def _suffix():
        tt, live = _ragged_suffix_tile(j - n_pages, q0, Wq, start, ln, tile)

        @pl.when(live)
        def _live():
            x = tt * tile + jax.lax.broadcasted_iota(
                jnp.int32, (1, tile), 1)
            valid = (x >= start) & (x < start + ln) & (x <= wq)
            if window is not None:
                valid &= x > (wq - window)
            _ragged_fold(q_ref, sk_ref[...].astype(jnp.float32),
                         sv_ref[...].astype(jnp.float32), valid, Hkv,
                         acc_ref, m_ref, l_ref)

    @pl.when(meets & (j == n_steps - 1))
    def _finalize():
        denom = jnp.maximum(l_ref[:, :, :1], 1e-30)
        out = (acc_ref[...] / denom).reshape(Hkv, Wq, G, D)
        out = out.transpose(1, 0, 2, 3).reshape(Wq, Hq, D)
        w_iota = q0 + jax.lax.broadcasted_iota(jnp.int32, (Wq, 1, 1), 0)
        mine = (w_iota >= start) & (w_iota < start + ln)
        o_ref[...] = jnp.where(mine, out.astype(o_ref.dtype), o_ref[...])


@functools.partial(jax.jit, static_argnames=("window", "tile", "interpret"))
def ragged_paged_prefill_attention_quant(
    q: jnp.ndarray,           # [W, Hq, D] packed query stream
    sfx_k: jnp.ndarray,       # [W, Hkv, D] packed suffix K (full precision)
    sfx_v: jnp.ndarray,
    k_pages: jnp.ndarray,     # [P, ps, Hkv, D] int8 single-layer pool
    k_scale: jnp.ndarray,     # [P, Hkv] f32
    v_pages: jnp.ndarray,
    v_scale: jnp.ndarray,
    row_tables: jnp.ndarray,  # [R, maxp] int32 page ids per wave row
    starts: jnp.ndarray,      # [R] int32 — row r's offset in the stream
    lens: jnp.ndarray,        # [R] int32 — row r's token count (0 = dead)
    prefix_lens: jnp.ndarray,  # [R] int32 — tokens already in r's pages
    window=None,
    tile: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Quantized ragged paged prefill attention; returns [W, Hq, D]."""
    n_tok = q.shape[0]
    q, sfx_k, sfx_v = _pad_stream(tile, q, sfx_k, sfx_v)
    W, Hq, D = q.shape
    P, ps, Hkv, _ = k_pages.shape
    R, maxp = row_tables.shape
    G = Hq // Hkv
    Tq = min(tile, W)         # W is a whole number of blocks
    n_st = W // Tq
    table = row_tables.astype(jnp.int32)
    starts = starts.astype(jnp.int32)
    lens = lens.astype(jnp.int32)
    plens = prefix_lens.astype(jnp.int32)
    ks3 = k_scale.reshape(P, 1, Hkv)
    vs3 = v_scale.reshape(P, 1, Hkv)

    def q_map(qb, r, j, table_ref, starts_ref, lens_ref, plens_ref):
        return (qb, 0, 0)

    def sfx_map(qb, r, j, table_ref, starts_ref, lens_ref, plens_ref):
        tt, _ = _ragged_suffix_tile(j - maxp, qb * Tq, Tq, starts_ref[r],
                                    lens_ref[r], Tq)
        return (tt, 0, 0)

    def page_of(qb, r, j, table_ref, starts_ref, lens_ref, plens_ref):
        meets = _ragged_row_meets_block(qb * Tq, Tq, starts_ref[r],
                                        lens_ref[r])
        last_live = _last_live_page(plens_ref[r], ps)
        return table_ref[r, jnp.where(meets, jnp.minimum(j, last_live), 0)]

    def kv_map(qb, r, j, table_ref, starts_ref, lens_ref, plens_ref):
        return (page_of(qb, r, j, table_ref, starts_ref, lens_ref,
                        plens_ref), 0, 0, 0)

    def sc_map(qb, r, j, table_ref, starts_ref, lens_ref, plens_ref):
        return (page_of(qb, r, j, table_ref, starts_ref, lens_ref,
                        plens_ref), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n_st, R, maxp + n_st),
        in_specs=[
            pl.BlockSpec((Tq, Hq, D), q_map),
            pl.BlockSpec((Tq, Hkv, D), sfx_map),
            pl.BlockSpec((Tq, Hkv, D), sfx_map),
            pl.BlockSpec((1, ps, Hkv, D), kv_map),
            pl.BlockSpec((1, 1, Hkv), sc_map),
            pl.BlockSpec((1, ps, Hkv, D), kv_map),
            pl.BlockSpec((1, 1, Hkv), sc_map),
        ],
        # swarmlint: revisit[r] -- every (r, j) step of a query block
        # accumulates into its one resident output block; the masked
        # finalize under pl.when(j == n_steps - 1) writes each row's
        # lanes exactly once
        out_specs=pl.BlockSpec((Tq, Hq, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((Hkv, Tq * G, D), jnp.float32),    # acc
            pltpu.VMEM((Hkv, Tq * G, 128), jnp.float32),  # running max
            pltpu.VMEM((Hkv, Tq * G, 128), jnp.float32),  # running denom
        ],
    )
    out = pl.pallas_call(
        functools.partial(_ragged_prefill_kernel_quant, page_size=ps,
                          n_kv_heads=Hkv, n_pages=maxp, window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((W, Hq, D), q.dtype),
        interpret=interpret,
    )(table, starts, lens, plens, q, sfx_k, sfx_v,
      k_pages, ks3, v_pages, vs3)
    return out[:n_tok]


# ---------------------------------------------------------------------------
# Latent (MLA) pages: absorbed attention over ONE pool of rows.
#
# A latent configuration's pool is ``[P, ps, Wd]`` (models/deepseek.py): a
# token's row ``[c_kv | k_pe | 0..]`` a layer, no heads axis, no values. In
# the absorbed form every query head is as wide as a row and scores against
# the same row, and the output is the softmax-weighted sum of the rows
# themselves: multi-query attention whose keys ARE its values. The two
# kernels below are the walks of `_paged_chunk_attn_kernel` and
# `_ragged_prefill_kernel` over that one pool: a page is copied once and
# serves as key and as value, all query heads (and, in a wave, ``tile_q``
# tokens of them) are the rows of one MXU operand, the operands stay in
# the pool's dtype with float32 accumulation, and the softmax scale rides
# in the queries (it carries YaRN's ``mscale^2``, so it is not
# ``1 / sqrt(width)``).


def _mla_fold(q, keys, valid, acc_ref, m_ref, l_ref):
    """Fold ``keys`` [Tk, Wd], which are also the values, into the online
    softmax of the query rows ``q`` [M, Wd]; ``valid`` [M | 1, Tk]."""
    s = jax.lax.dot_general(q, keys, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # [M, Tk]
    s = jnp.where(valid, s, -1e30)
    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    l_new = l_ref[:, :1] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(keys.dtype), keys, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def _mla_pages_per_block(page_size: int, maxp: int) -> int:
    return max(1, min(_PAGE_BLOCK_TOKENS // page_size, maxp))


def _mla_chunk_attn_kernel(table_ref, start_ref, step_ref, q_ref, pool_hbm,
                           ck_ref, o_ref, buf_ref, sem_ref, acc_ref, m_ref,
                           l_ref, *, page_size: int, pages_per_block: int):
    """Grid (B,), a slot a step, live or not: a row's frozen prefix
    walked in blocks of ``pages_per_block`` pages through a double buffer
    (a row's walk in `_paged_chunk_attn_kernel`: trips from the
    prefetched ``starts``, pages past the last live one never fetched),
    then the chunk's rows (entries 0..step), one online softmax."""
    b = pl.program_id(0)
    start = start_ref[b]
    step = step_ref[0]
    ps, ppb = page_size, pages_per_block
    tile = ppb * ps
    maxp = table_ref.shape[1]
    live_pages = jnp.minimum(
        jax.lax.div(jax.lax.max(start, 0) + (ps - 1), jnp.int32(ps)), maxp)
    n_blocks = jax.lax.div(live_pages + (ppb - 1), jnp.int32(ppb))

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, -1e30)
    l_ref[...] = jnp.zeros_like(l_ref)
    q = q_ref[0]                                          # [Hq, Wd]

    def page_copy(blk, slot, i):
        return pltpu.make_async_copy(
            pool_hbm.at[table_ref[b, blk * ppb + i]],
            buf_ref.at[slot, pl.ds(i * ps, ps)], sem_ref.at[slot])

    def fetch(blk, slot):
        for i in range(ppb):
            live = blk * ppb + i < live_pages

            @pl.when(live)
            def _start():
                page_copy(blk, slot, i).start()

            @pl.when(jnp.logical_not(live))
            def _blank():
                # not fetched: masked as a key, and finite as a value
                buf_ref[slot, pl.ds(i * ps, ps)] = jnp.zeros(
                    (ps,) + buf_ref.shape[2:], buf_ref.dtype)

    def wait(blk, slot):
        for i in range(ppb):
            @pl.when(blk * ppb + i < live_pages)
            def _wait():
                page_copy(blk, slot, i).wait()

    @pl.when(n_blocks > 0)
    def _first():
        fetch(0, 0)

    def block(blk, carry):
        slot = jax.lax.rem(blk, 2)

        @pl.when(blk + 1 < n_blocks)
        def _next():
            fetch(blk + 1, 1 - slot)

        wait(blk, slot)
        pos = blk * tile + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        _mla_fold(q, buf_ref[slot], pos < start, acc_ref, m_ref, l_ref)
        return carry

    jax.lax.fori_loop(0, n_blocks, block, 0)

    Kc = ck_ref.shape[1]
    idx = jax.lax.broadcasted_iota(jnp.int32, (1, Kc), 1)
    _mla_fold(q, ck_ref[0], idx <= step, acc_ref, m_ref, l_ref)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
                ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mla_paged_decode_attention_chunked(
    q: jnp.ndarray,           # [B, Hq, Wd] absorbed, scaled queries
    pages: jnp.ndarray,       # [P, ps, Wd] FROZEN latent pool (or flat)
    page_table: jnp.ndarray,  # [B, maxp] int32
    chunk: jnp.ndarray,       # [B, Kc, Wd] the chunk's rows so far
    starts: jnp.ndarray,      # [B] int32 frozen prefix length
    step: jnp.ndarray,        # scalar int32 index within the chunk
    interpret: bool = False,
) -> jnp.ndarray:
    """Absorbed decode attention over latent pages in place; [B, Hq, Wd],
    whose first ``kv_lora_rank`` lanes the caller takes through
    ``W_kvb^V``. A live page is copied once a call."""
    B, Hq, Wd = q.shape
    _, ps, _ = pages.shape
    maxp = page_table.shape[1]
    Kc = chunk.shape[1]
    ppb = _mla_pages_per_block(ps, maxp)
    q = q.astype(pages.dtype)
    chunk = chunk.astype(pages.dtype)

    def q_map(b, table_ref, start_ref, step_ref):
        return (b, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, Hq, Wd), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec((1, Kc, Wd), q_map),
        ],
        out_specs=pl.BlockSpec((1, Hq, Wd), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, ppb * ps, Wd), pages.dtype),   # the two halves
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((Hq, Wd), jnp.float32),            # acc
            pltpu.VMEM((Hq, 128), jnp.float32),           # running max
            pltpu.VMEM((Hq, 128), jnp.float32),           # running denom
        ],
    )
    return pl.pallas_call(
        functools.partial(_mla_chunk_attn_kernel, page_size=ps,
                          pages_per_block=ppb),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hq, Wd), q.dtype),
        name="mla_paged_decode_attention_chunked",
        interpret=interpret,
    )(page_table.astype(jnp.int32), starts.astype(jnp.int32),
      jnp.reshape(step, (1,)).astype(jnp.int32), q, pages, chunk)


def _mla_ragged_prefill_kernel(table_ref, starts_ref, lens_ref, plens_ref,
                               q_ref, sfx_hbm, pool_hbm, o_ref, buf_ref,
                               sem_ref, acc_ref, m_ref, l_ref, *,
                               page_size: int, pages_per_block: int,
                               tile_k: int):
    """Grid (nQ, R): query block ``qb`` (``Tq`` tokens, every head: the
    ``Tq * Hq`` rows of one operand) against wave row ``r``. A row that
    meets the block walks its live cached pages, ``pages_per_block`` a
    trip, then the stream's tiles of ``tile_k`` rows from the row's first
    to the block's own, through one double buffer; a row that does not
    meet the block does nothing (`_ragged_prefill_kernel`'s walk)."""
    qb = pl.program_id(0)
    r = pl.program_id(1)
    Tq, Hq, Wd = q_ref.shape
    ps, ppb = page_size, pages_per_block
    blk = ppb * ps
    T = buf_ref.shape[1]
    maxp = table_ref.shape[1]
    start = starts_ref[r]
    ln = lens_ref[r]
    plen = plens_ref[r]
    q0 = qb * Tq

    @pl.when(r == 0)
    def _zero_out():
        o_ref[...] = jnp.zeros_like(o_ref)
        buf_ref[...] = jnp.zeros_like(buf_ref)

    @pl.when(_ragged_row_meets_block(q0, Tq, start, ln))
    def _row():
        live_pages = jnp.minimum(
            jax.lax.div(jax.lax.max(plen, 0) + (ps - 1), jnp.int32(ps)),
            maxp)
        n_pref = jax.lax.div(live_pages + (ppb - 1), jnp.int32(ppb))
        first = jax.lax.div(start, jnp.int32(tile_k))
        last = jax.lax.div(
            jax.lax.max(jnp.minimum(start + ln, q0 + Tq) - 1, 0),
            jnp.int32(tile_k))
        n_trips = n_pref + (last - first + 1)

        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        q = q_ref[...].reshape(Tq * Hq, Wd)

        def copies(t, slot, act):
            @pl.when(t < n_pref)
            def _pages():
                def page(i, carry):
                    act(pltpu.make_async_copy(
                        pool_hbm.at[table_ref[r, t * ppb + i]],
                        buf_ref.at[slot, pl.ds(i * ps, ps)],
                        sem_ref.at[slot]))
                    return carry

                jax.lax.fori_loop(
                    0, jnp.minimum(live_pages - t * ppb, ppb), page, 0)

            @pl.when(t >= n_pref)
            def _tile():
                act(pltpu.make_async_copy(
                    sfx_hbm.at[pl.ds((first + t - n_pref) * tile_k, tile_k)],
                    buf_ref.at[slot, pl.ds(0, tile_k)], sem_ref.at[slot]))

        # stream index of each query row (token-major, a head a row)
        wq = q0 + jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (Tq * Hq, 1), 0),
            jnp.int32(Hq))
        kidx = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
        far = 1 << 30

        copies(0, 0, lambda cp: cp.start())

        def trip(t, carry):
            slot = jax.lax.rem(t, 2)

            @pl.when(t + 1 < n_trips)
            def _next():
                copies(t + 1, 1 - slot, lambda cp: cp.start())

            copies(t, slot, lambda cp: cp.wait())
            # a cached trip's keys are positions t*blk + i of the row,
            # valid below ``plen``; a stream trip's are stream indices,
            # valid inside the row and not after the query
            pre = t < n_pref
            kx = kidx + jnp.where(pre, t * blk,
                                  (first + t - n_pref) * tile_k)
            valid = ((kx >= jnp.where(pre, 0, start))
                     & (kx < jnp.where(pre, plen, start + ln))
                     & (kx <= wq + jnp.where(pre, far, 0)))
            if blk < T or tile_k < T:
                valid &= kidx < jnp.where(pre, blk, tile_k)
            _mla_fold(q, buf_ref[slot], valid, acc_ref, m_ref, l_ref)
            return carry

        jax.lax.fori_loop(0, n_trips, trip, 0)

        out = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
               ).reshape(Tq, Hq, Wd)
        w_iota = q0 + jax.lax.broadcasted_iota(jnp.int32, (Tq, 1, 1), 0)
        mine = (w_iota >= start) & (w_iota < start + ln)
        o_ref[...] = jnp.where(mine, out.astype(o_ref.dtype), o_ref[...])


# query tokens a block of the latent prefill kernel: with every head they
# are the rows of its MXU operands (16 x 128 heads = 2,048 at the published
# widths; the float32 accumulator is then 5.2 MB of VMEM)
_MLA_TILE_Q = 16


@functools.partial(jax.jit, static_argnames=("interpret",))
def mla_ragged_prefill_attention(
    q: jnp.ndarray,           # [W, Hq, Wd] absorbed, scaled query stream
    sfx: jnp.ndarray,         # [W, Wd] the wave's own rows, stream order
    pages: jnp.ndarray,       # [P, ps, Wd] latent pool (or flat [L*P, ..])
    row_tables: jnp.ndarray,  # [R, maxp] int32
    starts: jnp.ndarray,      # [R] int32 row offset in the stream
    lens: jnp.ndarray,        # [R] int32 row token count (0 = dead)
    prefix_lens: jnp.ndarray,  # [R] int32 tokens already in the pages
    interpret: bool = False,
) -> jnp.ndarray:
    """Absorbed attention of a packed wave over its rows' cached latent
    pages in place and over its own rows; [W, Hq, Wd] (zero where no row
    owns the position)."""
    n_tok, Hq, Wd = q.shape
    _, ps, _ = pages.shape
    R, maxp = row_tables.shape
    # stream tiles of a lane width of keys, or the whole of a narrow wave
    # in whole query blocks
    tile_k = min(_PAGE_BLOCK_TOKENS, -(-n_tok // _MLA_TILE_Q) * _MLA_TILE_Q)
    pad = (-n_tok) % tile_k
    if pad:
        q = jnp.pad(q, ((0, pad), (0, 0), (0, 0)))
        sfx = jnp.pad(sfx, ((0, pad), (0, 0)))
    W = q.shape[0]
    Tq = _MLA_TILE_Q
    ppb = _mla_pages_per_block(ps, maxp)
    keys = max(ppb * ps, tile_k)
    q = q.astype(pages.dtype)
    sfx = sfx.astype(pages.dtype)

    def q_map(qb, r, table_ref, starts_ref, lens_ref, plens_ref):
        return (qb, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(W // Tq, R),
        in_specs=[
            pl.BlockSpec((Tq, Hq, Wd), q_map),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        # swarmlint: revisit[r] -- every row step of a query block writes
        # into its one resident output block; the masked finalize at the
        # end of a row's walk writes each row's lanes exactly once
        out_specs=pl.BlockSpec((Tq, Hq, Wd), q_map),
        scratch_shapes=[
            pltpu.VMEM((2, keys, Wd), pages.dtype),       # the two halves
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((Tq * Hq, Wd), jnp.float32),       # acc
            pltpu.VMEM((Tq * Hq, 128), jnp.float32),      # running max
            pltpu.VMEM((Tq * Hq, 128), jnp.float32),      # running denom
        ],
    )
    block = Tq * Hq * Wd
    out = pl.pallas_call(
        functools.partial(_mla_ragged_prefill_kernel, page_size=ps,
                          pages_per_block=ppb, tile_k=tile_k),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((W, Hq, Wd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the query and output blocks twice, the accumulator and the
            # float32 temporaries of a fold of its size, the key halves
            vmem_limit_bytes=(4 * block * q.dtype.itemsize + 4 * block * 4
                              + 6 * Tq * Hq * 128 * 4
                              + 2 * keys * Wd * 2 + (8 << 20))),
        name="mla_ragged_prefill_attention",
        interpret=interpret,
    )(row_tables.astype(jnp.int32), starts.astype(jnp.int32),
      lens.astype(jnp.int32), prefix_lens.astype(jnp.int32), q, sfx, pages)
    return out[:n_tok]
