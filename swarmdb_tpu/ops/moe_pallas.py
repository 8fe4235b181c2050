"""Pallas TPU kernel: a routed layer's expert FFN where the hit experts'
bytes are the cost: a decode step and the narrower prefill waves.

A decode step multiplies a few rows (the engine's ``max_batch``, 32 in
``lfm2-8b-a1b.chat``) against every expert some live row chose: 22 MB of
weights an expert at the published widths against 0.7 GFLOP, memory bound
by 7 to 1. ``stream_experts`` is one call a layer that walks the compacted
list of hit experts and streams their ``w_gate``, ``w_up``, ``w_down`` tiles
through the pipeline's double buffer, the next expert's first tile in
flight while this one computes: no conditional an expert, no fusion an
expert (``lfm2.moe_block``'s loop: 32 ``lax.cond`` round three small
matmuls, each started cold; PERF.md section 6, PR 37). Up to 512 rows an
expert's matmuls take no longer than its bytes (57 us of the MXU at the
peak against 27 of HBM at 512 rows), and the same walk wins there too.

Layout (grid = (E, F / tile_f), both sequential):
- x      [N, D]            every row, resident for the whole call
- gate   [N, E] float32    a chosen expert's renormalised score, 0 else
- w_gate, w_up [E | n * E, D, F], w_down [.., F, D]: as they are stored.
  Grid step ``(i, f)`` names block ``base + hit_ids[i]``, F tile ``f``; a
  step past ``n_hit`` names the block already resident and fetches nothing
- base, n_hit, hit_ids [E] in SMEM (scalar prefetch)

The arithmetic is ``lfm2._swiglu`` and ``moe_block``'s sum to the
operation: float32 out of every matmul, ``silu(g) * u`` in float32 and
rounded once where the down matmul reads it, the expert's output gated and
summed in float32 in ascending expert order, rounded once at the end. Only
the down matmul's reduction is split where F is tiled.

A layer of UN-GATED experts (``w_gate`` None: ``W_2 relu(W_1 x)^2``,
``lfm2._relu2``) streams two matrices an expert and not three: the same
walk, grid and sum, one fetch fewer a tile. (Such a family keeps an F that
is no lane multiple, 1856, at the next one, 1920, the upper columns zero:
``models/nemotron_h.py``.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# rows up to which the kernel takes the call: it beat the loop by 14-40%
# from 32 rows to 512 and by 3-14% above, where every row is multiplied
# against every expert and a sorted form halves both
# (scripts/race_moe_dispatch.py --waves; PERF.md section 6, PR 37)
MAX_ROWS = 512
# the F tile, where it divides F: half an expert a grid step at the
# published width, 22 MB of double buffer (the race: 896 and 1792 tie to
# 256 rows, 896 wins by 16% at 512, 256 loses by 1-9%)
TILE_F = 896
_ROW_PAD = 16       # a bf16 tile's sublanes


def hit_list(hit: jnp.ndarray):
    """``(n_hit [1], hit_ids [E])`` int32 of ``hit`` [E] bool: the hit
    experts first, ascending, the tail repeating the last hit id (0 where
    none is hit), so that a grid step past ``n_hit`` names the block the
    step before it left resident."""
    E = hit.shape[0]
    e = jnp.arange(E, dtype=jnp.int32)
    pos = jnp.cumsum(hit.astype(jnp.int32)) - 1            # rank among hits
    # ids[j] = the hit expert of rank j
    ids = jnp.sum(jnp.where(hit[None, :] & (pos[None, :] == e[:, None]),
                            e[None, :], 0), axis=1)
    n_hit = jnp.sum(hit.astype(jnp.int32))
    last = jnp.max(jnp.where(hit, e, 0))
    return n_hit.reshape(1), jnp.where(e < n_hit, ids, last)


def takes(n_rows: int, x_dtype, w_up: jnp.ndarray) -> bool:
    """Whether the kernel takes a call of ``n_rows`` rows against these
    expert matrices (``w_up`` [.., D, F]), by what the call itself shows:
    rows few enough that the hit experts' bytes are the cost, bf16 rows
    and weights, widths that are lane multiples, and a TPU to run on."""
    _, D, F = w_up.shape
    return (n_rows <= MAX_ROWS and x_dtype == jnp.bfloat16
            and w_up.dtype == jnp.bfloat16
            and D % LANES == 0 and F % LANES == 0 and _on_tpu())


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def tile_of(F: int) -> int:
    """The F tile for an expert of width ``F``: ``TILE_F`` where it
    divides F, else the largest lane multiple under it that does."""
    if F % LANES:
        return F
    t = min(TILE_F, F)
    while F % t:
        t -= LANES
    return t


def _stream_kernel(base_ref, nhit_ref, ids_ref, x_ref, gate_ref, *refs,
                   n_f, gated):
    # refs: (w_gate,) w_up, w_down, out, the two float32 scratches
    wg_ref = refs[0] if gated else None
    wu_ref, wd_ref, o_ref, acc_ref, part_ref = refs[gated:]
    i, f = pl.program_id(0), pl.program_id(1)
    f32 = jnp.float32

    @pl.when((i == 0) & (f == 0))
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(i < nhit_ref[0])
    def _expert():
        x = x_ref[...]
        if gated:
            h = jax.nn.silu(jnp.dot(x, wg_ref[...],
                                    preferred_element_type=f32)) \
                * jnp.dot(x, wu_ref[...], preferred_element_type=f32)
        else:
            h = jnp.square(jnp.maximum(
                jnp.dot(x, wu_ref[...], preferred_element_type=f32), 0.0))
        y = jnp.dot(h.astype(x.dtype), wd_ref[...],
                    preferred_element_type=f32)
        gate = gate_ref[...]
        lane = jax.lax.broadcasted_iota(jnp.int32, gate.shape, 1)
        ge = jnp.sum(jnp.where(lane == ids_ref[i], gate, 0.0), axis=1,
                     keepdims=True)                        # [N, 1]
        if n_f == 1:
            acc_ref[...] += y * ge
        else:
            # the expert's output whole before its gate, as the loop has it
            @pl.when(f == 0)
            def _first():
                part_ref[...] = y

            @pl.when(f > 0)
            def _more():
                part_ref[...] += y

            @pl.when(f == n_f - 1)
            def _gated():
                acc_ref[...] += part_ref[...] * ge

    @pl.when((i == pl.num_programs(0) - 1) & (f == n_f - 1))
    def _out():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_f", "interpret"))
def stream_experts(
    x: jnp.ndarray,        # [N, D] the rows
    gate: jnp.ndarray,     # [N, E] float32, 0 where not chosen or dead
    hit: jnp.ndarray,      # [E] bool, the experts some live row chose
    w_gate,                # [E | n * E, D, F]; None: un-gated relu² experts
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,   # [E | n * E, F, D]
    base=0,                # this layer's first row of a flat stack
    tile_f=None,
    interpret: bool = False,
) -> jnp.ndarray:
    """The gated sum over the hit experts of ``_swiglu(x, expert)``
    (``_relu2`` where ``w_gate`` is None), ``[N, D]`` in ``x.dtype``; zeros
    where no expert is hit."""
    N, D = x.shape
    E = gate.shape[1]
    F = w_up.shape[2]
    gated = w_gate is not None
    ins = 2 + gated                # the matrices an expert streams
    tf = tile_of(F) if tile_f is None else tile_f
    if F % tf:
        raise ValueError(f"tile_f {tf} does not divide F {F}")
    n_f = F // tf
    rows = -(-N // _ROW_PAD) * _ROW_PAD
    if rows != N:
        x = jnp.pad(x, ((0, rows - N), (0, 0)))
        gate = jnp.pad(gate, ((0, rows - N), (0, 0)))
    n_hit, ids = hit_list(hit)
    base = jnp.reshape(base, (1,)).astype(jnp.int32)

    def rows_map(i, f, base_ref, nhit_ref, ids_ref):
        return (0, 0)

    def tile(i, f, nhit_ref):
        # past the last hit expert: the tile the last live step left
        return jnp.where(i < nhit_ref[0], f, n_f - 1)

    def in_map(i, f, base_ref, nhit_ref, ids_ref):
        return (base_ref[0] + ids_ref[i], 0, tile(i, f, nhit_ref))

    def down_map(i, f, base_ref, nhit_ref, ids_ref):
        return (base_ref[0] + ids_ref[i], tile(i, f, nhit_ref), 0)

    buffers = 2 * ins * D * tf * w_up.dtype.itemsize
    resident = rows * D * (4 * x.dtype.itemsize + 8) + 3 * rows * tf * 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(E, n_f),
        in_specs=[
            pl.BlockSpec((rows, D), rows_map),
            pl.BlockSpec((rows, E), rows_map),
            *[pl.BlockSpec((None, D, tf), in_map)] * (ins - 1),
            pl.BlockSpec((None, tf, D), down_map),
        ],
        # swarmlint: revisit[i] -- every step sums into the float32
        # scratch; the last step alone writes the output block
        out_specs=pl.BlockSpec((rows, D), rows_map),
        scratch_shapes=[
            pltpu.VMEM((rows, D), jnp.float32),    # the sum over experts
            pltpu.VMEM((rows, D), jnp.float32),    # one expert's output
        ],
    )
    out = pl.pallas_call(
        functools.partial(_stream_kernel, n_f=n_f, gated=gated),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=buffers + resident + (8 << 20)),
        name="moe_stream_experts",
        interpret=interpret,
    )(base, n_hit, ids, x, gate.astype(jnp.float32),
      *((w_gate,) if gated else ()), w_up, w_down)
    return out[:N]
