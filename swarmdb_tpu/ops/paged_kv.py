"""Block-paged KV cache (SURVEY §5.7 / §7 design hook, made real).

The round-1 cache was a dense ``[L, B, max_seq, Hkv, D]`` slot buffer: HBM
scales with ``max_batch x max_seq`` regardless of occupancy, which caps
batch x context well below what the 100-agent config needs (VERDICT r1
missing #2). Here K/V live in a shared POOL of fixed-size pages:

    k_pages, v_pages: [L, num_pages, page_size, Hkv, D]
    page_table:       [B, pages_per_slot] int32  (page ids per slot)

HBM is provisioned for the EXPECTED total live tokens (num_pages x
page_size), not worst-case ``B x S``. A host-side :class:`PageAllocator`
hands pages to slots at admission and reclaims them at retirement.

Pool invariants (all enforced here and in the engine):
- Page 0 is the TRASH page: never allocated. Inactive/retired slots keep a
  zeroed page-table row, so the decode step's masked garbage writes land in
  page 0 instead of corrupting pages that were freed and reallocated.
- Decode writes at positions >= max_seq are routed to the trash page (the
  dense cache dropped them via out-of-bounds scatter semantics; the paged
  indirection would otherwise CLAMP the page column and overwrite live
  entries).
- A retired slot's pages are freed only AFTER its page-table row is zeroed
  (``PageAllocator.flush_frees`` pairs the two), closing the
  stale-table/reused-page race.

All device functions are shape-static and jit-safe. The XLA attention path
gathers the slot's pages into a dense view (same HBM traffic as the dense
cache — correctness fallback); the bandwidth win on TPU comes from the
ragged Pallas kernel in ``ops/attention_pallas.py`` which reads only live
pages. No reference counterpart (the reference has no model code); pattern
follows the ragged paged attention design noted in PAPERS.md.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from ..utils.sync import make_lock

PagedCache = Dict[str, jnp.ndarray]  # {"k", "v", "page_table"}


# --------------------------------------------------- quantized KV pages
# SWARMDB_KV_DTYPE picks the POOL storage dtype (ISSUE 18): f32 and bf16
# store pages verbatim (bf16 = today's default, bit-identical); int8
# stores symmetric per-page-per-head quantized pages with f32 scales
# alongside — decode's roofline bytes halve, and the hot kernels
# dequantize IN-KERNEL (ops/attention_pallas.py) so full-precision KV
# never round-trips through HBM. Applies to PAGED pools only; dense slot
# caches and the dense prefix side pool ignore the flag.

KV_DTYPES = {"f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8}

#: logical dtype a quantized pool represents — dequantized reads and
#: suffix-KV casts target this, matching the unquantized default
DEQUANT_DTYPE = jnp.bfloat16

#: quantized-pool range: symmetric [-127, 127], leaving int8's -128 free
#: for the page sanitizer's canary (never produced by the quantizer)
_QMAX = 127.0


class QuantPool(NamedTuple):
    """A quantized page pool: int8 payload + f32 symmetric scales.

    ``data``  [..., P, ps, Hkv, D] int8 — quantized K or V pages
    ``scale`` [..., P, Hkv]        f32  — per-page-per-head scale;
              dequantized value = data * scale. Leading axes mirror the
              payload's (a per-layer slice of an [L, ...] pool carries
              its per-layer scale slice — ``lax.scan`` over the pool
              slices both, since NamedTuples are pytrees).

    Stored under the same ``{"k", "v"}`` cache keys as a plain pool, so
    the engine's fused dispatches, donation, warmup specs, and sharded
    cache plumbing are structure-transparent; code that touches the raw
    arrays goes through the ``pool_*`` helpers below.
    """

    data: jnp.ndarray
    scale: jnp.ndarray


class NoValuePool(NamedTuple):
    """What a latent cache (``models/deepseek.py``) holds under ``"v"``:
    nothing. Its one pool of rows ``[L, P, ps, Wd]`` sits under ``"k"``
    (every head's keys, and its first lanes the values). A page format is
    a type here, as ``QuantPool`` is: ``llama.init_paged_cache`` decides
    it once from the configuration, the helpers below take the latent path
    for this type alone, and a value pool that is None by a fault fails
    where it is used. An empty pytree, so the engine's programs hand it on
    as they hand on values and donate or write nothing for it."""


def kv_dtype_name() -> str:
    """Resolve SWARMDB_KV_DTYPE (default ``bf16`` — today's pool dtype,
    bit-identical with the flag unset)."""
    name = os.environ.get("SWARMDB_KV_DTYPE", "bf16").strip().lower()
    if name in ("", "auto"):
        return "bf16"
    if name not in KV_DTYPES:
        raise ValueError(
            f"SWARMDB_KV_DTYPE={name!r}: expected one of "
            f"{sorted(KV_DTYPES)}")
    return name


def kv_quantized(name: Optional[str] = None) -> bool:
    return (name or kv_dtype_name()) == "int8"


def is_quantized(pool: Any) -> bool:
    return isinstance(pool, QuantPool)


def pool_data(pool: Any) -> jnp.ndarray:
    """Raw storage array of a pool (int8 payload for quantized pools)."""
    return pool.data if isinstance(pool, QuantPool) else pool


def pool_dtype(pool: Any) -> jnp.dtype:
    """LOGICAL dtype of a pool — what reads dequantize to, and what
    suffix K/V should be cast to before attending (the write-what-you-
    attend contract of forward_ragged_prefill)."""
    return DEQUANT_DTYPE if isinstance(pool, QuantPool) else pool.dtype


def pool_layer(pool: Any, l: int) -> Any:
    """Layer ``l``'s slice of an [L, ...] pool. NOTE: plain ``pool[l]``
    on a :class:`QuantPool` is NamedTuple FIELD indexing (returns the
    payload array), not a layer slice — always go through here (inside
    ``lax.scan`` the pytree leaves are sliced per layer automatically,
    so scanned model code needs no change)."""
    if isinstance(pool, QuantPool):
        return QuantPool(pool.data[l], pool.scale[l])
    return pool[l]


def pool_flat(pool: Any) -> Any:
    """Flatten the leading (L, P) axes to one L*P page axis — the view
    the ragged/prefix forwards address with per-layer table offsets. A
    reshape on both payload and scales, never a copy."""
    if isinstance(pool, NoValuePool):
        return pool
    if isinstance(pool, QuantPool):
        d, s = pool.data, pool.scale
        return QuantPool(d.reshape((-1,) + d.shape[2:]),
                         s.reshape((-1,) + s.shape[2:]))
    return pool.reshape((-1,) + pool.shape[2:])


def pools_flat(pool_k: Any, pool_v: Any) -> Tuple[Any, Any, int, int]:
    """``(k_flat, v_flat, L, P)`` of a K/V pool pair: layer ``l``'s page
    ``p`` is page ``l * P + p`` of the flat views, so a layer scan hands
    the attention dispatchers ``table + l * P`` and reads the pool in
    place — scanning the pool itself makes XLA copy each layer's slice
    out before a kernel can take it. ``P`` is the pool's own (under
    ``shard_map``: the shard's local) page count; page 0 of every layer
    stays that layer's trash page."""
    L, P = pool_data(pool_k).shape[:2]
    return pool_flat(pool_k), pool_flat(pool_v), L, P


def pool_page_bytes(pool: Any) -> int:
    """HBM bytes ONE page id of this pool occupies ACROSS layers, scale
    rows included — prices swarmmem's warm-tier H2D model (a page's
    admission moves its slot in every layer). Accepts [L, P, ...] or
    single-layer [P, ...] pools; the divisor is always the page axis."""
    if isinstance(pool, NoValuePool):
        return 0
    if isinstance(pool, QuantPool):
        pages = int(pool.data.shape[-4])
        return (pool.data.nbytes + pool.scale.nbytes) // max(1, pages)
    pages = int(pool.shape[-4])
    return pool.nbytes // max(1, pages)


def _quantize_pages(vals: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric per-page-per-head quantization of full pages.

    ``vals`` [..., ps, Hkv, D] (any float dtype) -> (int8 [..., ps, Hkv,
    D], f32 scale [..., Hkv]). scale = amax(|page|, over token-slot and
    D) / 127; all-zero pages get a harmless positive scale (payload is
    zero either way, so dequantization is exact).
    """
    v = vals.astype(jnp.float32)
    amax = jnp.max(jnp.abs(v), axis=(-3, -1))            # [..., Hkv]
    scale = jnp.maximum(amax, 1e-30) / _QMAX
    q = jnp.clip(jnp.round(v / scale[..., None, :, None]), -_QMAX, _QMAX)
    return q.astype(jnp.int8), scale


def _dequantize_pages(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """f32 view of quantized pages: data [..., ps, Hkv, D] * scale
    [..., Hkv] (broadcast per head)."""
    return q.astype(jnp.float32) * scale[..., None, :, None]


def _requant_window(old_q: jnp.ndarray, old_s: jnp.ndarray,
                    new_v: jnp.ndarray, is_new: jnp.ndarray,
                    is_keep: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Shared requantization core for INCREMENTAL page writes.

    Whole-page writes quantize fresh values exactly; appends into a
    partially-filled page instead gather the touched pages, dequantize
    the SURVIVORS (``is_keep`` — slots before the write window), zero
    the stale slots (freed-page garbage / canaries must not poison the
    new amax), splice in the new tokens (``is_new``), and requantize the
    whole page. Requantizing an unchanged full page is idempotent (its
    amax slot re-rounds to +-127 exactly); when a new token raises the
    page amax, survivors re-round under the larger scale — a bounded,
    tolerance-tested error documented in README's quantization notes.

    ``old_q`` [..., ps, Hkv, D] int8, ``old_s`` [..., Hkv] f32,
    ``new_v`` broadcastable to [..., ps, Hkv, D] (float), ``is_new`` /
    ``is_keep`` [..., ps] bool. Returns the requantized (payload, scale).
    """
    old_f = _dequantize_pages(old_q, old_s)
    vals = jnp.where(is_new[..., None, None], new_v.astype(jnp.float32),
                     jnp.where(is_keep[..., None, None], old_f, 0.0))
    return _quantize_pages(vals)


def pagecheck_enabled() -> bool:
    """Runtime page sanitizer flag (obs/pagecheck.py, ISSUE 13)."""
    return os.environ.get("SWARMDB_PAGECHECK", "0") not in ("", "0")


def make_page_allocator(num_pages: int, page_size: int, max_seq: int,
                        batch: int, label: Optional[str] = None) -> Any:
    """Allocator factory — the page-pool twin of ``utils/sync.py``'s
    lock factory. Flag off (default): the plain :class:`PageAllocator`,
    the *exact* object callers constructed before the sanitizer existed
    (zero overhead, type identity pinned by tests/test_pagecheck.py).
    ``SWARMDB_PAGECHECK=1``: the checked subclass that mirrors every
    custody transition into the shadow registry."""
    if pagecheck_enabled():
        from ..obs import pagecheck

        return pagecheck.CheckedPageAllocator(
            num_pages, page_size, max_seq, batch, label=label)
    return PageAllocator(num_pages, page_size, max_seq, batch)


def make_sharded_page_allocator(pages_per_shard: int, n_shards: int,
                                page_size: int, max_seq: int,
                                batch: int,
                                label: Optional[str] = None) -> Any:
    if pagecheck_enabled():
        from ..obs import pagecheck

        return pagecheck.CheckedShardedPageAllocator(
            pages_per_shard, n_shards, page_size, max_seq, batch,
            label=label)
    return ShardedPageAllocator(pages_per_shard, n_shards, page_size,
                                max_seq, batch)


#: canary pattern stamped into freed pages' K/V under the sanitizer —
#: exactly representable in bf16/f32 (2^14), never produced by a real
#: forward pass at sane scales
CANARY_VALUE = -16384.0

#: int8 pools can't hold -16384: their canary is -128, the one int8 code
#: point the quantizer never emits (payload is clipped to [-127, 127])
INT8_CANARY_VALUE = -128

#: canary for a quantized pool's SCALE slots — real scales are strictly
#: positive by construction, so a write-after-free that recomputes a
#: page's scale always trips this even if the int8 payload collides
SCALE_CANARY_VALUE = -1.0


def canary_for(dtype: Any) -> float:
    """Dtype-derived canary value: the float canary where it's exactly
    representable, int8's reserved -128 code point on quantized pools."""
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        return float(INT8_CANARY_VALUE)
    return CANARY_VALUE


def canary_fill(k_pages: Any, v_pages: Any,
                page_ids: Sequence[int],
                value: Optional[float] = None) -> Tuple[Any, Any]:
    """Poison freed pages' device K/V with the canary (sanitizer-only
    path — an eager scatter per reclaim batch; the flag-off path never
    calls this). Quantized pools get BOTH slots poisoned: -128 in the
    int8 payload and -1.0 in the scale row."""
    ids = jnp.asarray(np.asarray(page_ids, np.int32))
    if isinstance(k_pages, QuantPool):
        dv = int(value) if value is not None else INT8_CANARY_VALUE
        k_pages = QuantPool(
            k_pages.data.at[:, ids].set(jnp.int8(dv)),
            k_pages.scale.at[:, ids].set(SCALE_CANARY_VALUE))
        v_pages = QuantPool(
            v_pages.data.at[:, ids].set(jnp.int8(dv)),
            v_pages.scale.at[:, ids].set(SCALE_CANARY_VALUE))
        return k_pages, v_pages
    fv = value if value is not None else canary_for(k_pages.dtype)
    k_pages = k_pages.at[:, ids].set(fv)
    if not isinstance(v_pages, NoValuePool):
        v_pages = v_pages.at[:, ids].set(fv)
    return k_pages, v_pages


def canary_check(k_pages: Any, v_pages: Any,
                 page_ids: Sequence[int],
                 value: Optional[float] = None) -> List[int]:
    """Page ids whose canary was OVERWRITTEN between free and
    re-allocation (a write-after-free landed in the pool). One host
    sync per verified allocation — sanitizer-only path. Quantized pools
    verify payload AND scale slots (a crime that rewrites either is
    caught)."""
    ids = np.asarray(page_ids, np.int32)
    if ids.size == 0:
        return []
    quant = isinstance(k_pages, QuantPool)
    if quant:
        dv = int(value) if value is not None else INT8_CANARY_VALUE
        kc = np.asarray(jax.device_get(k_pages.data[:, ids]))
        vc = np.asarray(jax.device_get(v_pages.data[:, ids]))
        ks = np.asarray(jax.device_get(k_pages.scale[:, ids]))
        vs = np.asarray(jax.device_get(v_pages.scale[:, ids]))
        bad: List[int] = []
        for i, p in enumerate(ids):
            ok = (np.all(kc[:, i] == dv) and np.all(vc[:, i] == dv)
                  and np.all(ks[:, i] == SCALE_CANARY_VALUE)
                  and np.all(vs[:, i] == SCALE_CANARY_VALUE))
            if not ok:
                bad.append(int(p))
        return bad
    fv = value if value is not None else canary_for(k_pages.dtype)
    kc = np.asarray(jax.device_get(k_pages[:, ids]))
    vc = kc if isinstance(v_pages, NoValuePool) else np.asarray(
        jax.device_get(v_pages[:, ids]))
    bad = []
    for i, p in enumerate(ids):
        if not (np.all(kc[:, i] == fv) and np.all(vc[:, i] == fv)):
            bad.append(int(p))
    return bad


def pages_per_slot(max_seq: int, page_size: int) -> int:
    return -(-max_seq // page_size)  # ceil


def init_paged_kv_cache(
    n_layers: int,
    num_pages: int,
    page_size: int,
    n_kv_heads: int,
    head_dim: int,
    batch: int,
    max_seq: int,
    dtype: Optional[jnp.dtype] = None,
) -> PagedCache:
    """Zeroed page pool + all-trash page table. ``num_pages`` INCLUDES the
    reserved trash page 0.

    ``dtype=None`` (the service default) resolves SWARMDB_KV_DTYPE:
    f32/bf16 give plain pools of that dtype, int8 gives :class:`QuantPool`
    entries under the same ``{"k", "v"}`` keys (int8 payload + zeroed f32
    scale rows — zero payload x any scale dequantizes to zero, matching
    the unquantized zero-init)."""
    if dtype is None:
        dtype = KV_DTYPES[kv_dtype_name()]
    shape = (n_layers, num_pages, page_size, n_kv_heads, head_dim)
    if jnp.issubdtype(jnp.dtype(dtype), jnp.integer):
        def _qpool() -> QuantPool:
            return QuantPool(
                jnp.zeros(shape, jnp.int8),
                jnp.zeros((n_layers, num_pages, n_kv_heads), jnp.float32))

        return {
            "k": _qpool(),
            "v": _qpool(),
            "page_table": jnp.zeros(
                (batch, pages_per_slot(max_seq, page_size)), jnp.int32
            ),
            "pos0": jnp.zeros((batch,), jnp.int32),
        }
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "page_table": jnp.zeros(
            (batch, pages_per_slot(max_seq, page_size)), jnp.int32
        ),
        # per-row RoPE offset: rope position = logical lane position +
        # pos0. Zero for ordinary requests; rolling-KV conversations
        # (StreamingLLM-style front-page drop) advance it so kept pages'
        # K — rope'd at their original absolute positions — stay
        # consistent with future queries
        "pos0": jnp.zeros((batch,), jnp.int32),
    }


def paged_gather_kv(
    k_pages: jnp.ndarray,   # [P, ps, Hkv, D] (single layer)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, maxp]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense [B, maxp*ps, Hkv, D] view of each slot's pages (XLA fallback
    attention input; bandwidth equals the dense cache, so use the Pallas
    ragged kernel on TPU for the savings)."""
    B, maxp = page_table.shape
    if isinstance(k_pages, QuantPool):
        # fallback dequant site: gather payload + scales, expand to a
        # dense f32 view (the XLA reference attends full precision; the
        # Pallas kernels dequantize per tile instead)
        ps = k_pages.data.shape[1]
        kg = _dequantize_pages(k_pages.data[page_table],
                               k_pages.scale[page_table])
        vg = _dequantize_pages(v_pages.data[page_table],
                               v_pages.scale[page_table])
        new_shape = (B, maxp * ps) + k_pages.data.shape[2:]
        return kg.reshape(new_shape), vg.reshape(new_shape)
    ps = k_pages.shape[1]
    kg = k_pages[page_table]  # [B, maxp, ps, Hkv, D]
    vg = v_pages[page_table]
    new_shape = (B, maxp * ps) + k_pages.shape[2:]
    return kg.reshape(new_shape), vg.reshape(new_shape)


def paged_insert_prefill(
    k_pages: jnp.ndarray,    # [L, P, ps, Hkv, D]
    v_pages: jnp.ndarray,
    dense_k: jnp.ndarray,    # [L, Bp, bucket, Hkv, D] prefill temp cache
    dense_v: jnp.ndarray,
    target_pages: jnp.ndarray,  # [n, bucket/ps] int32 page ids per admitted row
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter the first n rows of a dense bucket prefill cache into pages.

    ``bucket`` must be a multiple of the page size (buckets are powers of
    two >= page_size by construction). REFERENCE implementation: the
    engine's hot path performs this scatter inside its fused paged
    prefill (`Engine._prefill_paged_fused`); tests check that fused path
    against this standalone form."""
    L = pool_data(k_pages).shape[0]
    ps = pool_data(k_pages).shape[2]
    n, chunks = target_pages.shape
    bucket = dense_k.shape[2]
    assert bucket == chunks * ps, (bucket, chunks, ps)
    tail = dense_k.shape[3:]
    # [L, n, chunks, ps, Hkv, D] -> scatter chunks into the page axis
    kc = dense_k[:, :n].reshape((L, n * chunks, ps) + tail)
    vc = dense_v[:, :n].reshape((L, n * chunks, ps) + tail)
    flat = target_pages.reshape(-1)  # [n*chunks]
    k_pages = pool_insert_pages(k_pages, flat, kc)
    v_pages = pool_insert_pages(v_pages, flat, vc)
    return k_pages, v_pages


def pool_insert_pages(pool: Any, flat_ids: jnp.ndarray,
                      dense_pages: jnp.ndarray) -> Any:
    """WHOLE-page insert: ``dense_pages`` [L, n, ps, Hkv, D] full
    precision -> pool pages at ``flat_ids`` [n]. On quantized pools this
    is the EXACT quantization path (per-page amax over the fresh values
    only — no survivor requant); the engine's fused paged prefill and
    prefix-insert closures route their page scatters through here."""
    if isinstance(pool, QuantPool):
        q, s = _quantize_pages(dense_pages)
        return QuantPool(pool.data.at[:, flat_ids].set(q),
                         pool.scale.at[:, flat_ids].set(s))
    return pool.at[:, flat_ids].set(dense_pages.astype(pool.dtype))


def pool_gather_pages(pool: Any, ids: Sequence[int]) -> Any:
    """RAW payload of ``ids`` pages across all layers, as host numpy.

    The warm-tier spill format (ISSUE 19): pages leave the device at
    STORAGE width — int8 payload + f32 scales on quantized pools (the
    page spills at half the bf16 byte cost), pool dtype verbatim on
    plain pools. Reinserting the same payload via :func:`pool_insert_raw`
    is bit-identical: no dequant/requant round trip happens in either
    direction.

    Returns ``(data [L, n, ps, Hkv, D], scale [L, n, Hkv])`` numpy
    tuple for :class:`QuantPool`, else a single ``[L, n, ps, Hkv, D]``
    numpy array. Caller must run this on the engine thread — the gather
    reads pool buffers that engine jits donate.
    """
    n = len(ids)
    # pad the index to the next power of two with the trash page (0):
    # an advanced-index gather compiles per index LENGTH, and demotion
    # victims come in arbitrary page counts — unpadded, every new count
    # is a fresh XLA compile on the admission/eviction path (measured
    # as multi-ms stalls riding warm-hit TTFT). Pow2 padding bounds the
    # variants at ~log2(pool) per dtype; the pad rows are sliced off
    # host-side below.
    padded = max(1, 1 << (n - 1).bit_length()) if n else 1
    idx = np.zeros(padded, np.int32)
    idx[:n] = list(ids)
    if isinstance(pool, QuantPool):
        return (np.asarray(jax.device_get(pool.data[:, idx]))[:, :n],
                np.asarray(jax.device_get(pool.scale[:, idx]))[:, :n])
    return np.asarray(jax.device_get(pool[:, idx]))[:, :n]


def pool_insert_raw(pool: Any, flat_ids: jnp.ndarray, payload: Any) -> Any:
    """Reinsert a :func:`pool_gather_pages` payload at ``flat_ids``.

    The warm-tier promotion primitive: payload is already at storage
    width, so the insert is a plain ``.at[].set`` — the EXACT bytes that
    left the pool come back (quantized pools: int8 + scales set
    separately, no requantization). jit-safe; the engine wraps this in a
    donated dispatch so promotion rides the same buffer-reuse path as
    prefill inserts.
    """
    if isinstance(pool, QuantPool):
        q, s = payload
        return QuantPool(
            pool.data.at[:, flat_ids].set(jnp.asarray(q, jnp.int8)),
            pool.scale.at[:, flat_ids].set(jnp.asarray(s, jnp.float32)))
    return pool.at[:, flat_ids].set(jnp.asarray(payload, pool.dtype))


def paged_write_chunk(
    k_pages: jnp.ndarray,    # [L, P, ps, Hkv, D]
    v_pages: jnp.ndarray,
    chunk_k: jnp.ndarray,    # [L, B, Kc, Hkv, D] a finished decode chunk
    chunk_v: jnp.ndarray,
    start_positions: jnp.ndarray,  # [B] absolute position of chunk step 0
    page_table: jnp.ndarray,       # [B, maxp]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fold a finished decode chunk's K/V into the page pool — ONE bulk
    scatter per chunk instead of one per step (the paged counterpart of
    ops/layers.merge_chunk_kv).

    Trash-page invariants (see the module's): positions past the table's
    coverage (chunk overshoot on full lanes; the engine keeps max_seq a
    page multiple so this cap == max_seq) and rows with zeroed
    (retired/inactive) table entries land in trash page 0 and are never
    read.
    """
    L = pool_data(k_pages).shape[0]
    ps = pool_data(k_pages).shape[2]
    B, maxp = page_table.shape
    Kc = chunk_k.shape[2]
    if isinstance(k_pages, QuantPool):
        # requant window: the chunk spans at most ceil((ps-1+Kc)/ps)
        # consecutive page columns from start//ps. Survivors are slots
        # before start; slots past the chunk end are stale -> zeroed.
        npc = min(maxp, (Kc + 2 * ps - 2) // ps)
        start = start_positions.astype(jnp.int32)
        c0 = jnp.clip(start // ps, 0, maxp - 1)                  # [B]
        cols = c0[:, None] + jnp.arange(npc, dtype=jnp.int32)    # [B, npc]
        colc = jnp.clip(cols, 0, maxp - 1)
        page = jnp.take_along_axis(page_table, colc, axis=1)     # [B, npc]
        touched = (cols < maxp) & (cols * ps < (start + Kc)[:, None])
        page = jnp.where(touched, page, 0)                       # -> trash
        slots = jnp.arange(ps, dtype=jnp.int32)
        slot_pos = cols[..., None] * ps + slots                  # [B, npc, ps]
        t = slot_pos - start[:, None, None]                      # chunk index
        is_new = (t >= 0) & (t < Kc) & (slot_pos < maxp * ps)
        is_keep = slot_pos < start[:, None, None]
        tc = jnp.clip(t, 0, Kc - 1)
        bidx = jnp.arange(B)[:, None, None]
        pf = page.reshape(-1)                                    # [B*npc]
        out = []
        for pool, chunk in ((k_pages, chunk_k), (v_pages, chunk_v)):
            new_v = chunk[:, bidx, tc]           # [L, B, npc, ps, Hkv, D]
            q, s = _requant_window(pool.data[:, page],
                                   pool.scale[:, page],
                                   new_v, is_new, is_keep)
            out.append(QuantPool(
                pool.data.at[:, pf].set(
                    q.reshape((L, B * npc) + q.shape[3:])),
                pool.scale.at[:, pf].set(
                    s.reshape((L, B * npc) + s.shape[3:]))))
        return out[0], out[1]
    if isinstance(v_pages, NoValuePool):
        return _latent_write_chunk(k_pages, chunk_k, start_positions,
                                   page_table), v_pages
    pos = start_positions[:, None] + jnp.arange(Kc, dtype=jnp.int32)[None, :]
    col = jnp.minimum(pos // ps, maxp - 1)
    page = jnp.take_along_axis(page_table, col, axis=1)   # [B, Kc]
    page = jnp.where(pos < maxp * ps, page, 0)            # overshoot -> trash
    off = pos % ps
    pf, of = page.reshape(-1), off.reshape(-1)            # [B*Kc]
    tail = chunk_k.shape[3:]
    kc = chunk_k.reshape((L, B * Kc) + tail)
    vc = chunk_v.reshape((L, B * Kc) + tail)
    k_pages = k_pages.at[:, pf, of].set(kc.astype(k_pages.dtype))
    v_pages = v_pages.at[:, pf, of].set(vc.astype(v_pages.dtype))
    return k_pages, v_pages


def paged_write_ragged(
    k_pages: jnp.ndarray,    # [L, P, ps, Hkv, D]
    v_pages: jnp.ndarray,
    sfx_k: jnp.ndarray,      # [L, W, Hkv, D] packed wave K (stream order)
    sfx_v: jnp.ndarray,
    tok_row: jnp.ndarray,    # [W] int32 owning wave row (>= R = padding)
    tok_pos: jnp.ndarray,    # [W] int32 absolute position within the row
    row_tables: jnp.ndarray,  # [R, maxp] int32 page ids per wave row
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Positional per-token scatter of a PACKED ragged prefill wave's K/V
    into the page pool: stream token t lands at page
    ``row_tables[tok_row[t], tok_pos[t] // ps]`` offset ``tok_pos[t] %
    ps``. Padding tokens (row id out of range, or positions past the
    table's coverage) land in trash page 0 — the same invariants as
    :func:`paged_write_chunk`."""
    ps = pool_data(k_pages).shape[2]
    R, maxp = row_tables.shape
    if isinstance(k_pages, QuantPool):
        return _paged_write_ragged_quant(
            k_pages, v_pages, sfx_k, sfx_v, tok_row, tok_pos, row_tables)
    if isinstance(v_pages, NoValuePool):
        return _latent_write_ragged(k_pages, sfx_k, tok_row, tok_pos,
                                    row_tables), v_pages
    col = jnp.clip(tok_pos // ps, 0, maxp - 1)
    row = jnp.clip(tok_row, 0, R - 1)
    page = row_tables[row, col]                          # [W]
    dead = (tok_pos >= maxp * ps) | (tok_row < 0) | (tok_row >= R)
    page = jnp.where(dead, 0, page)
    off = jnp.where(dead, 0, tok_pos % ps)
    k_pages = k_pages.at[:, page, off].set(sfx_k.astype(k_pages.dtype))
    v_pages = v_pages.at[:, page, off].set(sfx_v.astype(v_pages.dtype))
    return k_pages, v_pages


# A latent pool (models/deepseek.py) is ``[L, P, ps, Wd]``: a token's row
# is one sublane of a page's tile, and the chip's compiler will not scatter
# below a tile in place: handed ``pool.at[:, page, off].set(rows)`` it
# copies the whole pool into a layout with the layer axis beside the lanes
# (padded 9 to 16: 3.44 GB beside a 1.93 GB pool, in every program; the AOT
# rehearsal of PR 44), writes there and copies back. So both writes below
# go by whole pages, as the int8 pool's requant windows do: the pages a
# write touches are gathered, the new rows laid into them, and the pages
# set back whole. A page untouched by the write names trash page 0.


def _latent_write_chunk(pool, chunk, start_positions, page_table):
    """A finished decode chunk's rows ``[L, B, Kc, Wd]`` into a latent
    pool: each lane's chunk spans at most ``ceil((ps - 1 + Kc) / ps)``
    page columns from ``start // ps``."""
    L, _, ps, Wd = pool.shape
    B, maxp = page_table.shape
    Kc = chunk.shape[2]
    npc = min(maxp, (Kc + 2 * ps - 2) // ps)
    start = start_positions.astype(jnp.int32)
    cols = (jnp.clip(start // ps, 0, maxp - 1)[:, None]
            + jnp.arange(npc, dtype=jnp.int32))                  # [B, npc]
    page = jnp.take_along_axis(page_table, jnp.clip(cols, 0, maxp - 1),
                               axis=1)
    touched = (cols < maxp) & (cols * ps < (start + Kc)[:, None])
    page = jnp.where(touched, page, 0)                           # -> trash
    slot_pos = cols[..., None] * ps + jnp.arange(ps, dtype=jnp.int32)
    t = slot_pos - start[:, None, None]                          # chunk index
    is_new = (t >= 0) & (t < Kc) & (slot_pos < maxp * ps)
    new = chunk[:, jnp.arange(B)[:, None, None], jnp.clip(t, 0, Kc - 1)]
    merged = jnp.where(is_new[None, ..., None], new.astype(pool.dtype),
                       pool[:, page])                 # [L, B, npc, ps, Wd]
    return pool.at[:, page.reshape(-1)].set(
        merged.reshape(L, B * npc, ps, Wd))


def _latent_write_ragged(pool, sfx, tok_row, tok_pos, row_tables):
    """A packed wave's rows ``[L, W, Wd]`` into a latent pool. A row's
    tokens are consecutive in the stream at consecutive positions, so the
    stream falls into runs, one a (row, page): a run begins at a live
    token that opens its row or a page; there are at most ``W // ps + R``
    of them. A run's page is gathered, the run laid into it from its
    first token's offset, and the page set back."""
    L, _, ps, Wd = pool.shape
    R, maxp = row_tables.shape
    W = tok_row.shape[0]
    live = (tok_row >= 0) & (tok_row < R) & (tok_pos < maxp * ps)
    before = jnp.pad(tok_row, (1, 0), constant_values=-1)[:W]
    begins = live & ((before != tok_row) | (tok_pos % ps == 0)
                     | ~jnp.pad(live, (1, 0))[:W])
    run_of = jnp.cumsum(begins.astype(jnp.int32)) - 1            # [W]
    S = W // ps + R
    (first,) = jnp.nonzero(begins, size=S, fill_value=W)         # [S]
    at = jnp.minimum(first, W - 1)
    page = jnp.where(
        first < W,
        row_tables[jnp.clip(tok_row[at], 0, R - 1),
                   jnp.clip(tok_pos[at] // ps, 0, maxp - 1)], 0)
    # page offset j of run s holds stream token first + j - off(first)
    idx = (first[:, None] + jnp.arange(ps, dtype=jnp.int32)[None]
           - (tok_pos[at] % ps)[:, None])                        # [S, ps]
    idc = jnp.clip(idx, 0, W - 1)
    mine = ((idx >= first[:, None]) & (idx < W) & live[idc]
            & (run_of[idc] == jnp.arange(S, dtype=jnp.int32)[:, None]))
    merged = jnp.where(mine[None, ..., None], sfx[:, idc].astype(pool.dtype),
                       pool[:, page])                     # [L, S, ps, Wd]
    return pool.at[:, page].set(merged)


def _paged_write_ragged_quant(
    k_pages: "QuantPool", v_pages: "QuantPool",
    sfx_k: jnp.ndarray, sfx_v: jnp.ndarray,
    tok_row: jnp.ndarray, tok_pos: jnp.ndarray,
    row_tables: jnp.ndarray,
) -> Tuple["QuantPool", "QuantPool"]:
    """Quantized ragged wave write: per-row requant window.

    Each wave row's tokens are CONTIGUOUS positions, so a row touches at
    most ceil(W/ps)+1 consecutive page columns starting at its first
    token's column (derived on-device via a segment-min over ``tok_pos``
    — the signature carries no per-row lengths). Survivors are slots
    before the row's first wave token (earlier chunks of a split prompt
    in the same partially-filled page); slots past the row's last wave
    token are stale -> zeroed. Prefix-cache HIT pages are page-aligned
    and sit strictly before every window, so shared pages are never
    rewritten. Untouched window columns and dead/padding rows route to
    trash page 0. Write amplification vs the unquantized scatter is
    ~R x window pages (the wave path is compute-bound; documented in
    README's quantization notes).
    """
    L = k_pages.data.shape[0]
    ps = k_pages.data.shape[2]
    tail = k_pages.data.shape[3:]                         # (Hkv, D)
    R, maxp = row_tables.shape
    W = tok_pos.shape[0]
    big = maxp * ps
    live = ((tok_row >= 0) & (tok_row < R)
            & (tok_pos >= 0) & (tok_pos < big))
    rowc = jnp.clip(tok_row, 0, R - 1)
    row_min = jnp.full((R,), big, jnp.int32).at[rowc].min(
        jnp.where(live, tok_pos, big))
    row_max = jnp.full((R,), -1, jnp.int32).at[rowc].max(
        jnp.where(live, tok_pos, -1))
    npc = min(maxp, -(-W // ps) + 1)
    c0 = jnp.clip(row_min // ps, 0, maxp - 1)             # [R]
    cols = c0[:, None] + jnp.arange(npc, dtype=jnp.int32)  # [R, npc]
    colc = jnp.clip(cols, 0, maxp - 1)
    page = jnp.take_along_axis(row_tables, colc, axis=1)  # [R, npc]
    touched = (cols < maxp) & (cols * ps <= row_max[:, None])
    page = jnp.where(touched, page, 0)                    # -> trash
    # stage the packed wave into per-row dense windows (scatter; padding
    # tokens and out-of-window strays are dropped via OOB row index)
    rel = tok_pos - c0[rowc] * ps
    okw = live & (rel >= 0) & (rel < npc * ps)
    sr = jnp.where(okw, rowc, R)                          # R = dropped
    srel = jnp.where(okw, rel, 0)
    is_new = jnp.zeros((R, npc * ps), bool).at[sr, srel].set(
        True, mode="drop").reshape(R, npc, ps)
    slots = jnp.arange(ps, dtype=jnp.int32)
    slot_pos = cols[..., None] * ps + slots               # [R, npc, ps]
    is_keep = slot_pos < row_min[:, None, None]
    pf = page.reshape(-1)                                 # [R*npc]
    out = []
    for pool, sfx in ((k_pages, sfx_k), (v_pages, sfx_v)):
        stage = jnp.zeros((L, R, npc * ps) + tail, jnp.float32)
        stage = stage.at[:, sr, srel].set(
            sfx.astype(jnp.float32), mode="drop")
        new_v = stage.reshape((L, R, npc, ps) + tail)
        q, s = _requant_window(pool.data[:, page], pool.scale[:, page],
                               new_v, is_new, is_keep)
        out.append(QuantPool(
            pool.data.at[:, pf].set(q.reshape((L, R * npc) + q.shape[3:])),
            pool.scale.at[:, pf].set(
                s.reshape((L, R * npc) + s.shape[3:]))))
    return out[0], out[1]


@jax.jit
def _set_page_table_rows(pt, rows, values):
    # a named function, not a lambda: the engine loop dispatches this
    # every admission round, and a device trace names programs by it
    return pt.at[rows].set(values, mode="drop")


def set_page_table_rows(
    page_table: jnp.ndarray, rows, values
) -> jnp.ndarray:
    """Replace whole page-table rows (admission assigns, retirement zeroes).

    The host arrays are padded to the full batch with out-of-bounds row
    indices (dropped by the scatter): a shape per DISTINCT row count would
    compile up to max_batch variants, each a multi-second stall — the
    round-4 paged-prefix bench collapse was exactly these landing in the
    measured window."""
    B, maxp = page_table.shape
    rows = np.asarray(rows, np.int32)
    values = np.asarray(values, np.int32).reshape(len(rows), maxp)
    n = len(rows)
    if n < B:
        pad_rows = np.full(B, B, np.int32)       # B = out of bounds -> drop
        pad_rows[:n] = rows
        pad_vals = np.zeros((B, maxp), np.int32)
        pad_vals[:n] = values
        rows, values = pad_rows, pad_vals
    return _set_page_table_rows(page_table, rows, values)


def live_row_list(page_table: jnp.ndarray):
    """``(rows [B], n_live)``, both int32, of an UN-OFFSET page table:
    the slots that hold a sequence (a row that is not all trash: its first
    page is not page 0) first and in slot order, then the others. What
    the chunked decode kernel walks. Compares and sums, no sort: it runs
    once a decode step."""
    live = page_table[:, 0] != 0
    slot = jnp.arange(page_table.shape[0], dtype=jnp.int32)
    n_live = jnp.sum(live, dtype=jnp.int32)
    # a slot's place: the slots of its kind before it, the dead behind
    # the live
    same_before = ((slot[None, :] < slot[:, None])
                   & (live[None, :] == live[:, None]))
    place = (jnp.sum(same_before, axis=1, dtype=jnp.int32)
             + jnp.where(live, 0, n_live))
    rows = jnp.sum(jnp.where(place[None, :] == slot[:, None],
                             slot[None, :], 0), axis=1, dtype=jnp.int32)
    return rows, n_live


@dataclass
class _SlotPages:
    pages: List[int]


class PageAllocator:
    """Host-side page pool bookkeeping (engine admission/retirement path).

    Thread-safety: engine calls happen on the engine thread only, but the
    lock keeps stats()/external probes safe. Page 0 (trash) is never
    handed out.
    """

    def __init__(self, num_pages: int, page_size: int, max_seq: int,
                 batch: int) -> None:
        if num_pages < 2:
            raise ValueError("need at least 2 pages (page 0 is reserved)")
        self.page_size = page_size
        self.max_seq = max_seq
        self.maxp = pages_per_slot(max_seq, page_size)
        self.num_pages = num_pages
        self._by_slot: Dict[int, _SlotPages] = {}
        self._pending_free: List[int] = []  # slot ids retired, not yet flushed
        self._lock = make_lock("ops.paged_kv.PageAllocator._lock")
        self.batch = batch
        # cumulative churn (page-grant / page-return counts): two int
        # adds under the lock the public methods already hold — the
        # /metrics per-lane churn counters read these off stats()
        self.pages_allocated_total = 0
        self.pages_freed_total = 0
        # pool generation: bumped by every reset(). Page ids held OUTSIDE
        # the allocator (the serving layer's rolling-KV registry) are only
        # valid within the generation they were handed out in — a reset
        # reclaims the whole pool, so a stale holder resuming or freeing
        # them would alias another slot's pages (ADVICE r4 medium #2).
        self.generation = 0
        # swarmmem residency ledger (ISSUE 17): page alloc/free stamps
        # piggybacked on the critical sections below. Flag off -> the
        # shared NullPool, one no-op call per hook site.
        from ..obs.memprof import memprof

        self.mem = memprof().pool(self.stats)
        self._rebuild_free()

    # -- free-list geometry (the ONLY pieces the sharded subclass swaps) -----

    # swarmlint: holds[self._lock]
    def _rebuild_free(self) -> None:
        self._free: List[int] = list(range(self.num_pages - 1, 0, -1))

    # swarmlint: holds[self._lock]
    def _take(self, slot_id: int, n: int) -> Optional[List[int]]:
        """Pop ``n`` pages usable by ``slot_id``; None if uncoverable.
        Caller holds the lock."""
        if len(self._free) < n:
            return None
        return [self._free.pop() for _ in range(n)]

    # swarmlint: holds[self._lock]
    def _give(self, page_ids: List[int]) -> None:
        """Return pages to the free list. Caller holds the lock."""
        self._free.extend(page_ids)

    def _check_prefix(self, slot_id: int, prefix_pages: List[int]) -> None:
        """Engine-bug guard hook: referenced (not owned) pages must be
        addressable by this slot. No constraint on the single pool."""

    # -- admission -----------------------------------------------------------

    def pages_needed(self, prompt_len: int, max_new: int, chunk: int) -> int:
        """Pages covering every position this request can ever WRITE:
        prompt + generated tokens + up to one chunk of overshoot, capped at
        max_seq (beyond-cap writes are trash-routed)."""
        worst = min(self.max_seq, prompt_len + max_new + chunk)
        return min(self.maxp, -(-worst // self.page_size))

    def can_allocate(self, n: int) -> bool:
        with self._lock:
            return len(self._free) >= n

    def allocate(self, slot_id: int, n: int) -> Optional[np.ndarray]:
        """Take n pages for a slot; None if the pool can't cover it.
        Returns the slot's FULL page-table row (maxp wide, trash-padded)."""
        with self._lock:
            if slot_id in self._by_slot:
                raise RuntimeError(f"slot {slot_id} already holds pages")
            pages = self._take(slot_id, n)
            if pages is None:
                return None
            self.pages_allocated_total += len(pages)
            self.mem.page_alloc(pages)
            self._by_slot[slot_id] = _SlotPages(pages)
            row = np.zeros(self.maxp, np.int32)
            row[: len(pages)] = pages
            return row

    # swarmlint: borrows[page]: prefix_pages
    def allocate_with_prefix(self, slot_id: int, prefix_pages: List[int],
                             n_fresh: int) -> Optional[np.ndarray]:
        """Row = ``prefix_pages`` (cache-custody pages the slot only
        REFERENCES — the prefix cache pins them; they are not recorded in
        ``_by_slot`` and retirement does not free them) followed by
        ``n_fresh`` newly owned pages. None if the pool can't cover the
        fresh part."""
        with self._lock:
            if slot_id in self._by_slot:
                raise RuntimeError(f"slot {slot_id} already holds pages")
            self._check_prefix(slot_id, prefix_pages)
            fresh = self._take(slot_id, n_fresh)
            if fresh is None:
                return None
            self.pages_allocated_total += len(fresh)
            self.mem.page_alloc(fresh)
            self._by_slot[slot_id] = _SlotPages(fresh)
            row = np.zeros(self.maxp, np.int32)
            pages = list(prefix_pages) + fresh
            row[: len(pages)] = pages
            return row

    def transfer_to_cache(self, slot_id: int, page_ids: List[int]) -> None:
        """Remove ``page_ids`` from a slot's OWNED set: custody moves to
        the prefix cache (registration), so retirement won't free them."""
        with self._lock:
            sp = self._by_slot.get(slot_id)
            if sp is not None:
                drop = set(page_ids)
                sp.pages = [p for p in sp.pages if p not in drop]

    def add_free(self, page_ids: List[int]) -> None:
        """Return cache-evicted pages to the pool (prefix-cache eviction
        path; the caller guarantees no live slot references them)."""
        with self._lock:
            self.pages_freed_total += len(page_ids)
            self.mem.page_free(page_ids)
            self._give(page_ids)

    def reserve(self, n: int) -> List[int]:
        """Withdraw up to ``n`` free pages from circulation (serving
        chaos: pool-squeeze fault). Reserved pages are never referenced
        by any table row — the fault only starves admission, exactly
        like a burst of long-lived occupants. Return them with
        :meth:`add_free` (the heal path); a reset() reclaims them
        implicitly (the ids die with the generation)."""
        with self._lock:
            take = min(n, len(self._free))
            out = [self._free.pop() for _ in range(take)]
            self.mem.page_alloc(out)
            return out

    def free_count(self, slot_id: Optional[int] = None) -> int:
        """Free pages available — to ``slot_id`` if given (the sharded
        allocator restricts each slot to its shard's sub-pool)."""
        with self._lock:
            return len(self._free)

    def pages_for(self, slot_id: int) -> List[int]:
        with self._lock:
            sp = self._by_slot.get(slot_id)
            return list(sp.pages) if sp else []

    def owns(self, slot_id: int) -> bool:
        """Whether the slot holds pages: an occupant's, or a retired
        occupant's that wait for the reclaim. ``allocate`` refuses such a
        slot."""
        with self._lock:
            return slot_id in self._by_slot

    # -- retirement ----------------------------------------------------------

    def mark_retired(self, slot_id: int) -> None:
        """Queue a slot's pages for reclaim. The pages stay OWNED (absorbing
        end-of-chunk garbage writes) until flush_frees() zeroes the slot's
        table row and returns them to the pool."""
        with self._lock:
            if slot_id in self._by_slot:
                self._pending_free.append(slot_id)

    def take_pending_frees(self) -> List[int]:
        """Drain the retired-slot queue WITHOUT freeing pages yet — the
        caller zeroes the slots' table rows on device first (possibly
        mirroring that update to pod workers), then calls
        :meth:`release_taken`. Split out of flush_frees so the engine can
        route the device update through its multihost mirror."""
        with self._lock:
            pending, self._pending_free = self._pending_free, []
        return pending

    def release_taken(self, pending: List[int]) -> None:
        """Free the pages of slots drained by take_pending_frees — only
        AFTER their table-row zeroing is enqueued on device: the device
        order (zero row -> later writes by a new owner) is program order."""
        with self._lock:
            for slot_id in pending:
                sp = self._by_slot.pop(slot_id, None)
                if sp is not None:
                    self.pages_freed_total += len(sp.pages)
                    self.mem.page_free(sp.pages)
                    self._give(list(reversed(sp.pages)))

    def requeue_pending(self, pending: List[int]) -> None:
        """Put a drained retirement batch BACK on the pending queue: the
        caller's table-row zeroing dispatch failed, so the pages must
        not be freed (their rows may still reference them) but must not
        be forgotten either — the next admission round retries. Found
        by swarmlint SWL801: a drained batch held across a raising
        dispatch with no requeue leaked its pages forever."""
        with self._lock:
            self._pending_free[:0] = pending

    def flush_frees(self, page_table: jnp.ndarray) -> jnp.ndarray:
        """Zero retired slots' table rows on device, then free their pages.
        Call at the START of each admission round."""
        pending = self.take_pending_frees()
        if not pending:
            return page_table
        rows = np.asarray(pending, np.int32)
        zeros = np.zeros((len(pending), self.maxp), np.int32)
        try:
            page_table = set_page_table_rows(page_table, rows, zeros)
        except Exception:
            # the rows were never zeroed: freeing now would reopen the
            # stale-table/reused-page race, dropping the batch would
            # leak it (SWL801) — requeue for the next round
            self.requeue_pending(pending)
            raise
        self.release_taken(pending)
        return page_table

    # -- DP-sharding hooks (no-ops for the single-pool allocator) ------------

    def usable_prefix(self, slot_id: int, hits: List[int]) -> int:
        """How many of ``hits`` (a prefix-cache chain, in order) this slot
        may reference. The single pool has no locality constraint."""
        return len(hits)

    def shard_of(self, slot_id: int) -> int:
        return 0

    def slot_capacity(self) -> int:
        """Most pages any single request can ever be granted — the
        admission-feasibility bound Engine.submit checks (a request
        needing more would wedge the no-skip-ahead admission queue
        forever)."""
        return self.num_pages - 1

    def evictable(self, slot_id: int):
        """Predicate for prefix-cache eviction on behalf of ``slot_id``:
        only pages that could actually cover its shortfall qualify. The
        single pool accepts any page (None = no filter)."""
        return None

    # -- introspection -------------------------------------------------------

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "num_pages": self.num_pages,
                "free_pages": len(self._free),
                "live_slots": len(self._by_slot),
                "page_size": self.page_size,
                "pages_allocated_total": self.pages_allocated_total,
                "pages_freed_total": self.pages_freed_total,
            }

    def reset(self) -> None:
        with self._lock:
            # bump BEFORE rebuilding the free list: a racing epoch check
            # must never observe (old generation, rebuilt pool)
            self.generation += 1
            self._rebuild_free()
            self._by_slot.clear()
            self._pending_free.clear()
            self.mem.pool_reset()


class ShardedPageAllocator(PageAllocator):
    """Slot→shard-affine page pool for DP-sharded paged serving
    (parallel/serving.py ``build_serving_engine(paged=True)``).

    Global page ids are STRIPED per data shard: shard ``k`` owns
    ``[k*Pl, (k+1)*Pl)`` (``Pl = pages_per_shard``), and slot ``s``
    belongs to shard ``s // (batch / n_shards)``. Every page a slot's
    table row references therefore lives in that slot's shard of the
    device pool (the pool array shards its PAGE axis over ``data``), so
    the shard_map'd decode step's gathers and scatters are purely
    shard-local — the SPMD decode program contains zero collectives and
    scales linearly over the data axis.

    Id ``k*Pl`` is shard-``k``'s TRASH page, never handed out: inside the
    shard_map the table is localized as ``clip(table - k*Pl, 0, Pl-1)``,
    which maps this shard's ids to ``[1, Pl)``, the global trash 0 (and
    any zeroed/retired row) to local 0, and can never alias a foreign
    shard's pages because foreign ids are simply not reachable from this
    shard's table rows.

    Inherits all retirement/custody bookkeeping (``_by_slot``,
    ``flush_frees``) from the base class — only the free-list geometry
    and the prefix-locality check change.
    """

    def __init__(self, pages_per_shard: int, n_shards: int, page_size: int,
                 max_seq: int, batch: int) -> None:
        if n_shards < 1 or batch % n_shards:
            raise ValueError(
                f"batch {batch} must divide over n_shards {n_shards}")
        if pages_per_shard < 2:
            raise ValueError("need >= 2 pages per shard (one is trash)")
        # geometry attrs BEFORE super().__init__ — it calls the overridden
        # _rebuild_free, which needs them
        self.n_shards = n_shards
        self.pages_per_shard = pages_per_shard
        self.slots_per_shard = batch // n_shards
        super().__init__(pages_per_shard * n_shards, page_size, max_seq,
                         batch)

    # -- free-list geometry (everything else is inherited) -------------------

    def _rebuild_free(self) -> None:
        # per-shard stacks; ids k*Pl (per-shard trash) are never free
        pl = self.pages_per_shard
        self._free_by_shard: List[List[int]] = [
            list(range((k + 1) * pl - 1, k * pl, -1))
            for k in range(self.n_shards)
        ]

    def _take(self, slot_id: int, n: int) -> Optional[List[int]]:
        free = self._free_by_shard[self.shard_of(slot_id)]
        if len(free) < n:
            return None
        return [free.pop() for _ in range(n)]

    def _give(self, page_ids: List[int]) -> None:
        for p in page_ids:
            self._free_by_shard[self.shard_of_page(p)].append(p)

    def _check_prefix(self, slot_id: int, prefix_pages: List[int]) -> None:
        shard = self.shard_of(slot_id)
        if any(self.shard_of_page(p) != shard for p in prefix_pages):
            # engine bug guard: usable_prefix() must have trimmed these
            raise RuntimeError(
                f"slot {slot_id} (shard {shard}) referencing foreign-"
                f"shard prefix pages {prefix_pages}")

    # -- shard geometry ------------------------------------------------------

    def shard_of(self, slot_id: int) -> int:
        return min(self.n_shards - 1, slot_id // self.slots_per_shard)

    def shard_of_page(self, page_id: int) -> int:
        return min(self.n_shards - 1, page_id // self.pages_per_shard)

    def slot_capacity(self) -> int:
        # a slot can only ever draw from its own shard's sub-pool
        return self.pages_per_shard - 1

    def evictable(self, slot_id: int):
        shard = self.shard_of(slot_id)
        return lambda p: self.shard_of_page(p) == shard

    def can_allocate(self, n: int) -> bool:
        with self._lock:
            return any(len(f) >= n for f in self._free_by_shard)

    def free_count(self, slot_id: Optional[int] = None) -> int:
        with self._lock:
            if slot_id is None:
                return sum(len(f) for f in self._free_by_shard)
            return len(self._free_by_shard[self.shard_of(slot_id)])

    def usable_prefix(self, slot_id: int, hits: List[int]) -> int:
        """Truncate a prefix-chain match at the first page outside the
        slot's shard: the shard_map'd decode can only address its own
        sub-pool, so a cross-shard reference would localize to a wrong
        page. (Chains register whole per-shard, so in practice a chain
        is either fully usable or fully foreign.)"""
        shard = self.shard_of(slot_id)
        n = 0
        for p in hits:
            if self.shard_of_page(p) != shard:
                break
            n += 1
        return n

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "num_pages": self.num_pages,
                "free_pages": sum(len(f) for f in self._free_by_shard),
                "free_by_shard": [len(f) for f in self._free_by_shard],
                "live_slots": len(self._by_slot),
                "page_size": self.page_size,
                "n_shards": self.n_shards,
                "pages_allocated_total": self.pages_allocated_total,
                "pages_freed_total": self.pages_freed_total,
            }


# --- kerncheck: descriptor + scatter-replay sanitizer (obs/kerncheck) ---
# SWARMDB_KERNCHECK=1 wraps the ragged wave scatter so every concrete
# call first audits its descriptors (live-token page OOB, trash-page
# targets, duplicate (page, offset) cells) and then replays the scatter
# in numpy against the returned pool. Flag off this block never runs —
# the module exports the plain function object (type identity pinned by
# tests/test_kernelcheck.py).
if os.environ.get("SWARMDB_KERNCHECK", "0") == "1":
    from ..obs.kerncheck import checked_paged_write_ragged

    paged_write_ragged = checked_paged_write_ragged(paged_write_ragged)
