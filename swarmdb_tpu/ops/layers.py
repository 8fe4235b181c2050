"""Shared transformer building blocks (JAX, TPU-first).

Functional ops used by the Llama and Mixtral families: RMSNorm, rotary
embeddings, grouped-query attention over a slot-based KV cache, SwiGLU.
No reference counterpart (the reference has no model code, SURVEY §2.4/§5.7).

TPU notes:
- matmuls/einsums stay bf16 (MXU native); normalization statistics and
  softmax run in fp32 for stability, logits are returned fp32.
- all shapes are static under jit; the KV cache is a fixed [B, S, ...] slot
  buffer and validity is expressed by masking, never by dynamic shapes.
- attention is plain einsum + masked softmax: XLA fuses this well on TPU.
  (A Pallas ragged/paged decode kernel is the planned replacement on the
  serving hot path once it lands in ``ops/``.)
"""

from __future__ import annotations

import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


import contextlib
import threading

_pallas_ctx = threading.local()


@contextlib.contextmanager
def pallas_disabled():
    """Trace-time override: sharded (TP) forwards wrap their model call in
    this so SWARMDB_PALLAS=1 cannot route a head-sharded KV cache through
    pallas_call, which has no partitioning rule and would force a gather
    of the whole cache every step (parallel/serving.py)."""
    prev = getattr(_pallas_ctx, "disabled", False)
    _pallas_ctx.disabled = True
    try:
        yield
    finally:
        _pallas_ctx.disabled = prev


def _pallas_decode_enabled() -> bool:
    """SWARMDB_PALLAS=1 routes single-token decode attention through the
    Pallas kernel (ops/attention_pallas.py); 0/unset keeps the XLA einsum
    path. Checked at trace time (static under jit)."""
    if getattr(_pallas_ctx, "disabled", False):
        return False
    return os.environ.get("SWARMDB_PALLAS", "0") == "1"


def _paged_pallas_enabled(kv_span: Optional[int] = None) -> bool:
    """The ragged paged kernel defaults ON for TPU in the LONG-context
    regime it exists for (HBM reads ∝ live pages). At short max_seq and
    full occupancy the XLA gather path wins — its big fused einsums fill
    the MXU where the kernel's per-page [G, ps] dots cannot (swarm100 on
    v5e at S=256: gather 2150 tok/s vs kernel 1484), so the TPU default
    flips to the kernel only when the table's coverage ``kv_span`` (maxp *
    page_size) reaches SWARMDB_PALLAS_KV_SPAN (default 1024 — the one
    v5e measurement above; retune the knob, not the code, when new
    silicon numbers land; the legacy SWARMDB_PALLAS_MIN_SEQ name is still
    honored). SWARMDB_PALLAS=0 forces the gather fallback everywhere,
    =1 forces the kernel even off-TPU (interpret mode — slow, for
    tests)."""
    if getattr(_pallas_ctx, "disabled", False):
        return False
    env = os.environ.get("SWARMDB_PALLAS", "")
    if env == "0":
        return False
    if env == "1":
        return True
    if jax.default_backend() != "tpu":
        return False
    if kv_span is None:
        return True
    thr = os.environ.get(
        "SWARMDB_PALLAS_KV_SPAN",
        os.environ.get("SWARMDB_PALLAS_MIN_SEQ", "1024"))
    return kv_span >= int(thr)


def decode_kernel_choice(kv_span: Optional[int] = None) -> str:
    """Host-side view of the decode-attention dispatch: ``"pallas"`` when
    the ragged paged kernel would serve a table of ``kv_span`` coverage,
    ``"gather"`` for the XLA page-gather fallback. The engine stamps this
    on flight-step records (and the bench on its mode record) so the
    analyzer can attribute a kernel-vs-gather regression instead of
    guessing which path a record measured."""
    return "pallas" if _paged_pallas_enabled(kv_span) else "gather"


def prefill_kernel_choice() -> str:
    """Host-side view of the ragged-prefill dispatch (the prefill twin
    of :func:`decode_kernel_choice`): ``"pallas-ragged"`` when
    ``ragged_prefill_dispatch`` would run the Pallas kernel,
    ``"xla-reference"`` for the dense fallback. swarmprof stamps this
    onto the ragged prefill variants' metadata at harvest time, so a
    profile dump says WHICH kernel its device seconds measured — the
    same record-provenance rule the bench's ``kernel`` field enforces
    for decode."""
    return ("pallas-ragged" if _ragged_prefill_kernel_enabled()
            else "xla-reference")


def _ragged_prefill_kernel_enabled() -> bool:
    """Gate for the ragged paged PREFILL kernel: SWARMDB_PALLAS=0 forces
    the XLA reference fallback, =1 forces the kernel even off-TPU
    (interpret mode — tests), default = kernel exactly on TPU. No
    kv-span crossover here: prefill waves amortize the page reads over
    the whole suffix, so the kernel's in-place page streaming wins as
    soon as there is any prefix at all and merely ties without one."""
    if getattr(_pallas_ctx, "disabled", False):
        return False
    env = os.environ.get("SWARMDB_PALLAS", "")
    if env == "0":
        return False
    if env == "1":
        return True
    return jax.default_backend() == "tpu"


def _record_static_vmem(kernel: str, key: str, dims) -> None:
    """Fold the SWL903 static VMEM estimate for ``kernel`` into
    swarmprof's variant table under ``key``. Runs at dispatch trace
    time, where every dim in the site's symbolic footprint is a
    concrete Python int. Best-effort by contract: profiler off, no
    matching pallas_call site, or an unbound dim all mean 'no
    estimate', never an error on the dispatch path."""
    from ..obs.profiler import profiler

    prof = profiler()
    if not prof.enabled:
        return
    try:
        from ..analysis.kernelcheck import estimate_vmem, vmem_budget

        est = estimate_vmem(kernel, dims)
        if est is None:
            return
        devs = jax.devices()
        kind = devs[0].device_kind if devs else ""
        prof.record_vmem_estimate(key, est, vmem_budget(kind))
    except Exception:  # accounting must never break dispatch
        pass


def paged_attention_dispatch_chunked(
    q: jnp.ndarray,           # [B, 1, Hq, D] decode query
    k_pages: jnp.ndarray,     # [P, ps, Hkv, D] single-layer pool (FROZEN)
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # [B, maxp]
    chunk_k: jnp.ndarray,     # [B, Kc, Hkv, D] this chunk's K so far
    chunk_v: jnp.ndarray,
    q_positions: jnp.ndarray,  # [B, 1]
    step: jnp.ndarray,        # scalar int32
    *,
    window: Optional[int] = None,
    live_rows=None,           # (rows [B], n_live) int32
) -> jnp.ndarray:
    """Two-segment decode attention for the PAGED cache: frozen page pool
    + in-chunk buffer under one softmax (the paged counterpart of
    ``gqa_attention_chunked``; the pool is only written once per chunk via
    ``ops.paged_kv.paged_write_chunk``).

    Ragged Pallas kernel on TPU (walks the slots of ``live_rows``, as
    ``ops.paged_kv.live_row_list`` makes them from the un-offset table,
    and reads only their live pages + chunk buffer; every other slot's
    output is exact zeros; without the list every slot is walked); XLA
    page-gather fallback elsewhere, which computes every slot — the
    fallback reuses ``gqa_attention_chunked`` directly on the gathered
    dense view, whose frozen-segment mask (kv_pos < chunk start) already
    expresses "pool holds strictly the prefix".
    """
    from .paged_kv import is_quantized, paged_gather_kv, pool_data

    kd = pool_data(k_pages)
    if _paged_pallas_enabled(page_table.shape[1] * kd.shape[1]):
        starts = (q_positions[:, 0] - step).astype(jnp.int32)
        interp = jax.default_backend() != "tpu"
        if is_quantized(k_pages):
            from .attention_pallas import (
                paged_decode_gqa_attention_chunked_quant)

            out = paged_decode_gqa_attention_chunked_quant(
                q[:, 0], k_pages.data, k_pages.scale,
                v_pages.data, v_pages.scale, page_table, chunk_k,
                chunk_v, starts, step.astype(jnp.int32),
                window=window, interpret=interp,
            )
            return out[:, None]
        from .attention_pallas import paged_decode_gqa_attention_chunked

        B = q.shape[0]
        _record_static_vmem(
            "_paged_chunk_attn_kernel", "kernel:pallas",
            {"B": B, "Hq": q.shape[2], "Hkv": kd.shape[2], "D": q.shape[3],
             "ps": kd.shape[1], "Kc": chunk_k.shape[1],
             "maxp": page_table.shape[1], "itemsize": kd.dtype.itemsize})
        if live_rows is None:
            live_rows = (jnp.arange(B, dtype=jnp.int32), jnp.int32(B))
        out = paged_decode_gqa_attention_chunked(
            q[:, 0], k_pages, v_pages, page_table, chunk_k, chunk_v,
            starts, step.astype(jnp.int32), *live_rows,
            window=window, interpret=interp,
        )
        return out[:, None]
    kg, vg = paged_gather_kv(k_pages, v_pages, page_table)
    return gqa_attention_chunked(q, kg, vg, chunk_k, chunk_v, q_positions,
                                 step, window=window)


def ragged_prefill_attention_reference(
    q: jnp.ndarray,           # [W, Hq, D] packed query stream
    sfx_k: jnp.ndarray,       # [W, Hkv, D] packed suffix K
    sfx_v: jnp.ndarray,
    k_pages: jnp.ndarray,     # [P, ps, Hkv, D] page pool (single layer)
    v_pages: jnp.ndarray,
    row_tables: jnp.ndarray,  # [R, maxp] int32
    starts: jnp.ndarray,      # [R] int32 stream offset per row
    lens: jnp.ndarray,        # [R] int32 suffix length per row (0 = dead)
    prefix_lens: jnp.ndarray,  # [R] int32 tokens already in the pages
    tok_row: jnp.ndarray,     # [W] int32 owning row per token (>= R = pad)
    *,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Dense XLA reference for the ragged paged prefill kernel — and its
    off-TPU fallback. Every packed token attends its own row's prefix
    pages (gathered dense, positions ``0..prefix_lens[r]``) plus the
    row's suffix tokens causally; one fp32 softmax spans both segments.
    Cross-row scores are masked via ``tok_row``; padding tokens (row id
    >= R) match no real row and produce garbage the caller discards.

    Materializes [W, Pt] gathered prefix KV and [W, Pt + W] fp32 scores —
    the densification the Pallas kernel exists to avoid; fine for CPU
    tests/fallback waves, wrong for silicon. Quantized pools dequantize
    after the table gather (same math the quant kernel runs per tile).
    Returns [W, Hq, D]."""
    from .paged_kv import _dequantize_pages, is_quantized, pool_data

    W, Hq, D = q.shape
    Hkv = sfx_k.shape[1]
    G = Hq // Hkv
    R, maxp = row_tables.shape
    ps = pool_data(k_pages).shape[1]
    Pt = maxp * ps

    row = jnp.clip(tok_row, 0, R - 1)
    if is_quantized(k_pages):
        kp = _dequantize_pages(
            k_pages.data[row_tables],
            k_pages.scale[row_tables]).reshape(R, Pt, Hkv, D)
        vp = _dequantize_pages(
            v_pages.data[row_tables],
            v_pages.scale[row_tables]).reshape(R, Pt, Hkv, D)
    else:
        kp = k_pages[row_tables].reshape(R, Pt, Hkv, D)
        vp = v_pages[row_tables].reshape(R, Pt, Hkv, D)
    kp_t = kp[row]                                       # [W, Pt, Hkv, D]
    vp_t = vp[row]

    qg = q.reshape(W, Hkv, G, D)
    s_p = jnp.einsum("wkgd,wpkd->wkgp", qg, kp_t,
                     preferred_element_type=jnp.float32)
    s_s = jnp.einsum("wkgd,xkd->wkgx", qg, sfx_k,
                     preferred_element_type=jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.float32(D))

    x = jnp.arange(W, dtype=jnp.int32)
    q_abs = prefix_lens[row] + x - starts[row]           # [W]
    p_pos = jnp.arange(Pt, dtype=jnp.int32)
    valid_p = p_pos[None, :] < prefix_lens[row][:, None]  # [W, Pt]
    if window is not None:
        valid_p &= p_pos[None, :] > (q_abs[:, None] - window)
    same = tok_row[:, None] == tok_row[None, :]          # [W, W]
    valid_s = same & (x[None, :] <= x[:, None])          # packed causal
    if window is not None:
        valid_s &= x[None, :] > (x[:, None] - window)

    s_p = jnp.where(valid_p[:, None, None, :], s_p * scale,
                    jnp.float32(-1e30))
    s_s = jnp.where(valid_s[:, None, None, :], s_s * scale,
                    jnp.float32(-1e30))
    s = jnp.concatenate([s_p, s_s], axis=-1)             # [W, Hkv, G, Pt+W]
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("wkgp,wpkd->wkgd", p[..., :Pt].astype(vp_t.dtype),
                     vp_t, preferred_element_type=jnp.float32)
    out = out + jnp.einsum("wkgx,xkd->wkgd",
                           p[..., Pt:].astype(sfx_v.dtype), sfx_v,
                           preferred_element_type=jnp.float32)
    return out.reshape(W, Hq, D).astype(q.dtype)


def ragged_wave_max_width(n_heads: int, n_kv_heads: int) -> Optional[int]:
    """The widest wave ``ragged_prefill_dispatch``'s kernel is built into
    (None: any width up to ``max_seq``). At 16 query heads a KV head the
    chip's compiler refused the wave programs of 2,048 tokens (the
    kernel's score tile is ``tile x G`` rows a KV head: 17.4 MB of VMEM
    where a call is given 16) and of 4,096 (it copied the 2-KV-head pool),
    and passed 1,024 (``benchmark/aot_rehearsal.py``, PR 50); at 4 a KV
    head it takes every width. A longer round takes more waves."""
    return 1024 if n_heads // n_kv_heads >= 16 else None


def ragged_prefill_dispatch(
    q: jnp.ndarray,           # [W, Hq, D] packed query stream
    sfx_k: jnp.ndarray,       # [W, Hkv, D]
    sfx_v: jnp.ndarray,
    k_pages: jnp.ndarray,     # [P, ps, Hkv, D]
    v_pages: jnp.ndarray,
    row_tables: jnp.ndarray,  # [R, maxp]
    starts: jnp.ndarray,
    lens: jnp.ndarray,
    prefix_lens: jnp.ndarray,
    tok_row: jnp.ndarray,     # [W]
    *,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Packed ragged PREFILL attention over the paged pool: the Pallas
    ragged kernel on TPU (prefix pages read in place via the page table —
    no gather densification, no bucket padding), the dense XLA reference
    elsewhere. Same TPU-gated / interpreter-tested pattern as the paged
    decode dispatchers above. Returns [W, Hq, D]."""
    if _ragged_prefill_kernel_enabled():
        from .paged_kv import is_quantized, pool_data

        quant = is_quantized(k_pages)
        W = q.shape[0]
        # the wrappers pad the stream to whole blocks (8-row sublane
        # quantum below one tile); tile 128 is their default
        _record_static_vmem(
            "_ragged_prefill_kernel_quant" if quant
            else "_ragged_prefill_kernel",
            f"prefill.ragged[w{W}]",
            {"W": W + (-W) % 8, "tile": 128, "Hq": q.shape[1],
             "Hkv": sfx_k.shape[1], "D": q.shape[2],
             "ps": pool_data(k_pages).shape[1],
             "maxp": row_tables.shape[1],
             "itemsize": pool_data(k_pages).dtype.itemsize})
        interp = jax.default_backend() != "tpu"
        if quant:
            from .attention_pallas import (
                ragged_paged_prefill_attention_quant)

            return ragged_paged_prefill_attention_quant(
                q, sfx_k, sfx_v, k_pages.data, k_pages.scale,
                v_pages.data, v_pages.scale, row_tables, starts, lens,
                prefix_lens, window=window, interpret=interp,
            )
        from .attention_pallas import ragged_paged_prefill_attention

        return ragged_paged_prefill_attention(
            q, sfx_k, sfx_v, k_pages, v_pages, row_tables, starts,
            lens, prefix_lens, window=window, interpret=interp,
        )
    return ragged_prefill_attention_reference(
        q, sfx_k, sfx_v, k_pages, v_pages, row_tables, starts, lens,
        prefix_lens, tok_row, window=window)


def rms_norm(x: jnp.ndarray, weight: jnp.ndarray, eps: float) -> jnp.ndarray:
    """RMSNorm with fp32 statistics, output in x.dtype."""
    x32 = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (x32 * inv).astype(x.dtype) * weight


def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    """Inverse frequencies [head_dim/2], fp32."""
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponent)


def rope_cos_sin(
    positions: jnp.ndarray, head_dim: int, theta: float
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Precompute RoPE rotation terms for a batch of positions.

    Returns (cos, sin), each [B, T, 1, D/2] fp32. Depends only on positions,
    so callers compute it ONCE per forward and reuse it across every layer —
    inside a scanned layer body XLA cannot hoist the transcendentals itself.
    """
    inv_freq = rope_frequencies(head_dim, theta)  # [D/2]
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # [B, T, D/2]
    return jnp.cos(angles)[:, :, None, :], jnp.sin(angles)[:, :, None, :]


def apply_rope(
    x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray
) -> jnp.ndarray:
    """Rotary position embedding with precomputed terms (`rope_cos_sin`).

    x: [B, T, H, D]. Pairs (x[..., :D/2], x[..., D/2:]) are rotated — the
    "split-half" convention used by HF Llama, so checkpoints interoperate.
    """
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def qkv_proj(
    h: jnp.ndarray,       # [B, T, D] normed hidden states
    lp: dict,             # layer params with "wq"/"wk"/"wv"
    n_heads: int,
    n_kv_heads: int,
    head_dim: int,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    norm_eps: float = 1e-5,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Q/K/V projections + head split + RoPE — the block every forward
    variant (dense, chunked, paged, seq-parallel, pipelined; every family
    alike) starts its attention with. A layer that has ``q_norm`` and
    ``k_norm`` ([head_dim]) takes an RMSNorm over the head size on q and
    k before RoPE."""
    B, T = h.shape[0], h.shape[1]
    q = jnp.einsum("btd,dh->bth", h, lp["wq"]).reshape(B, T, n_heads, head_dim)
    k = jnp.einsum("btd,dh->bth", h, lp["wk"]).reshape(B, T, n_kv_heads, head_dim)
    v = jnp.einsum("btd,dh->bth", h, lp["wv"]).reshape(B, T, n_kv_heads, head_dim)
    if "q_norm" in lp:
        q = rms_norm(q, lp["q_norm"], norm_eps)
        k = rms_norm(k, lp["k_norm"], norm_eps)
    return apply_rope(q, cos, sin), apply_rope(k, cos, sin), v


def swiglu(x: jnp.ndarray, w_gate: jnp.ndarray, w_up: jnp.ndarray,
           w_down: jnp.ndarray) -> jnp.ndarray:
    """SwiGLU MLP: silu(x @ gate) * (x @ up) @ down."""
    g = jax.nn.silu(jnp.einsum("btd,df->btf", x, w_gate))
    u = jnp.einsum("btd,df->btf", x, w_up)
    return jnp.einsum("btf,fd->btd", g * u, w_down)


def write_kv_cache(
    cache_k: jnp.ndarray,  # [B, S, Hkv, D]
    cache_v: jnp.ndarray,
    k: jnp.ndarray,  # [B, T, Hkv, D]
    v: jnp.ndarray,
    positions: jnp.ndarray,  # [B, T]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Write new K/V into per-slot cache rows at absolute positions.

    Positions may differ per batch row (continuous batching: each slot is
    at its own decode offset). Three lowerings, picked by static shape:

    - T == S (prefill filling its whole temp cache): the write IS the
      cache — return the new values directly, zero data movement.
    - T == 1 (decode): a positional mask + select. TPU lowers per-row
      scatter to a serialized index loop (measured: it dominated the
      round-3 decode step); the mask form is a pure vectorized
      element-wise op over the cache the step already streams through.
    - general T: the scatter fallback (no serving path hits this today).
    """
    B, S = cache_k.shape[0], cache_k.shape[1]
    T = k.shape[1]
    if T == S:
        return k.astype(cache_k.dtype), v.astype(cache_v.dtype)
    if T == 1:
        hit = jnp.arange(S)[None, :] == positions  # [B, S]
        sel = hit[:, :, None, None]
        cache_k = jnp.where(sel, k.astype(cache_k.dtype), cache_k)
        cache_v = jnp.where(sel, v.astype(cache_v.dtype), cache_v)
        return cache_k, cache_v
    b_idx = jnp.arange(B)[:, None]  # [B, 1]
    cache_k = cache_k.at[b_idx, positions].set(k.astype(cache_k.dtype))
    cache_v = cache_v.at[b_idx, positions].set(v.astype(cache_v.dtype))
    return cache_k, cache_v


def gqa_attention_chunked(
    q: jnp.ndarray,          # [B, 1, Hq, D] decode query
    cache_k: jnp.ndarray,    # [B, S, Hkv, D] FROZEN prefix cache
    cache_v: jnp.ndarray,
    chunk_k: jnp.ndarray,    # [B, Kc, Hkv, D] this chunk's K so far
    chunk_v: jnp.ndarray,
    q_positions: jnp.ndarray,  # [B, 1] absolute position of the query
    step: jnp.ndarray,       # scalar int32: index of this step in the chunk
    *,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Two-segment decode attention: frozen slot cache + in-chunk buffer.

    The engine's chunked decode (Engine._decode) keeps the big [B, S, ...]
    cache FROZEN for the K steps of a chunk and accumulates the chunk's own
    K/V in a tiny [B, Kc, ...] buffer written with dynamic_update_slice
    (uniform index — no per-row scatter). Attention therefore reads the
    big cache without ever rewriting it; the round-3 path rewrote the full
    cache every step, which profiling showed was the single largest cost
    of a decode chunk (~2x the model matmuls at batch 128).

    Masking: the frozen segment is valid strictly below the chunk's start
    position (entries at >= start are a previous occupant's garbage); the
    chunk segment is valid up to and including ``step``. One softmax spans
    both segments. Returns [B, 1, Hq, D].
    """
    B, S = cache_k.shape[0], cache_k.shape[1]
    Kc = chunk_k.shape[1]
    Hq, Hkv = q.shape[2], cache_k.shape[2]
    group = Hq // Hkv
    D = q.shape[-1]

    tile = min(S, 256)
    if _pallas_decode_enabled() and S % tile == 0:
        # round-4 silicon trace: the two einsums below run at 2.2x their
        # HBM floor and always read the FULL [S] lane; the kernel streams
        # tiles under an online softmax and skips the DMA past each
        # slot's live prefix, so traffic tracks occupancy
        from .attention_pallas import decode_gqa_attention_chunked

        out = decode_gqa_attention_chunked(
            q[:, 0], cache_k, cache_v, chunk_k, chunk_v,
            (q_positions[:, 0] - step).astype(jnp.int32), step,
            window=window, tile=tile,
            interpret=jax.default_backend() != "tpu",
        )
        return out[:, None]

    qg = q.reshape(B, 1, Hkv, group, D)
    s_f = jnp.einsum("btkgd,bskd->bkgts", qg, cache_k,
                     preferred_element_type=jnp.float32)
    s_c = jnp.einsum("btkgd,bskd->bkgts", qg, chunk_k,
                     preferred_element_type=jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.float32(D))

    start = q_positions - step                           # [B, 1] chunk start
    kv_pos = jnp.arange(S)[None, None, :]                # [1, 1, S]
    valid_f = kv_pos < start[:, :, None]                 # [B, 1, S]
    if window is not None:
        valid_f &= kv_pos > (q_positions[:, :, None] - window)
    j = jnp.arange(Kc)[None, None, :]                    # [1, 1, Kc]
    valid_c = j <= step                                  # [1, 1, Kc]
    abs_c = start[:, :, None] + j                        # [B, 1, Kc]
    if window is not None:
        valid_c = valid_c & (abs_c > (q_positions[:, :, None] - window))

    s_f = jnp.where(valid_f[:, None, None], s_f * scale, jnp.float32(-1e30))
    s_c = jnp.where(valid_c[:, None, None], s_c * scale, jnp.float32(-1e30))
    s = jnp.concatenate([s_f, s_c], axis=-1)             # [B, Hkv, g, 1, S+Kc]
    p = jax.nn.softmax(s, axis=-1)
    p_f = p[..., :S].astype(cache_v.dtype)
    p_c = p[..., S:].astype(chunk_v.dtype)
    out = jnp.einsum("bkgts,bskd->btkgd", p_f, cache_v,
                     preferred_element_type=jnp.float32)
    out = out + jnp.einsum("bkgts,bskd->btkgd", p_c, chunk_v,
                           preferred_element_type=jnp.float32)
    return out.reshape(q.shape).astype(q.dtype)


def merge_chunk_kv(
    cache_k: jnp.ndarray,   # [L, B, S, Hkv, D]
    cache_v: jnp.ndarray,
    chunk_k: jnp.ndarray,   # [L, B, Kc, Hkv, D]
    chunk_v: jnp.ndarray,
    start_positions: jnp.ndarray,  # [B] absolute position of chunk step 0
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fold a finished chunk's K/V back into the big slot cache — ONCE per
    chunk instead of once per step.

    Expressed as a one-hot einsum + select: ``sel[b, s, j] = 1`` iff cache
    position s is chunk entry j for row b. A take_along_axis gather here
    is numerically identical but XLA-TPU takes minutes to compile the 5D
    batched gather (measured >5 min at serving shapes vs ~1 s for this
    form); the einsum is a tiny MXU contraction and the one-hot rows are
    exact (exactly one 1 per written position), so no precision is lost.
    """
    S = cache_k.shape[2]
    Kc = chunk_k.shape[2]
    kv_pos = jnp.arange(S)[None, :]                      # [1, S]
    start = start_positions[:, None]                     # [B, 1]
    j = jnp.arange(Kc)[None, None, :]                    # [1, 1, Kc]
    sel = ((kv_pos - start)[:, :, None] == j)            # [B, S, Kc]
    hit = (kv_pos >= start) & (kv_pos < start + Kc)      # [B, S]
    sel_b = sel.astype(cache_k.dtype)
    hit_b = hit[None, :, :, None, None]

    def upd(full, chunk):
        g = jnp.einsum("bsj,lbjhd->lbshd", sel_b, chunk,
                       preferred_element_type=full.dtype)
        return jnp.where(hit_b, g, full)

    return upd(cache_k, chunk_k), upd(cache_v, chunk_v)


def merge_chunk_kv_scatter(
    cache_k: jnp.ndarray,   # [L, B, S, Hkv, D]
    cache_v: jnp.ndarray,
    chunk_k: jnp.ndarray,   # [L, B, Kc, Hkv, D]
    chunk_v: jnp.ndarray,
    start_positions: jnp.ndarray,  # [B]
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter formulation of ``merge_chunk_kv`` (numerically identical;
    `test_merge_chunk_scatter_matches_einsum`).

    One [B, Kc]-indexed `.at[].set` per cache tensor instead of the
    one-hot einsum + select. The chunk trace showed ~27 ms/chunk of
    merge + full-cache copies around the einsum form at B=128
    (PROFILE.md session 2); this form writes only the Kc columns and
    gives XLA a direct in-place-update pattern for the donated cache.
    TPU scatters serialize per index row, which is why the PER-STEP
    [B, 1] scatter lost badly in round 3 — per CHUNK the amortization
    may land differently. Raced on silicon by scripts/profile_merge.py;
    selected via SWARMDB_MERGE=scatter (backend/service.py)."""
    Kc = chunk_k.shape[2]
    b_idx = jnp.arange(cache_k.shape[1])[:, None]        # [B, 1]
    cols = start_positions[:, None] + jnp.arange(Kc)[None, :]  # [B, Kc]
    # a chunk may overshoot its lane (the engine dispatches full K-step
    # chunks and retires on max_seq at processing time): mode="drop"
    # discards the out-of-range columns, matching the einsum form's hit
    # mask (kv_pos < start + Kc never fires past S there)
    ck = cache_k.at[:, b_idx, cols].set(chunk_k.astype(cache_k.dtype),
                                        mode="drop")
    cv = cache_v.at[:, b_idx, cols].set(chunk_v.astype(cache_v.dtype),
                                        mode="drop")
    return ck, cv


def gqa_attention(
    q: jnp.ndarray,          # [B, T, Hq, D]
    cache_k: jnp.ndarray,    # [B, S, Hkv, D]
    cache_v: jnp.ndarray,    # [B, S, Hkv, D]
    q_positions: jnp.ndarray,  # [B, T] absolute position of each query
    *,
    window: Optional[int] = None,  # sliding-window size (None = full causal)
) -> jnp.ndarray:
    """Grouped-query attention against the full cache buffer with causal
    masking by absolute position.

    Validity invariant: a cache slot is filled monotonically from position 0,
    so every cache entry at position s <= q_position is live for that row.
    Returns [B, T, Hq, D] in q.dtype; softmax in fp32.
    """
    B, S = cache_k.shape[0], cache_k.shape[1]
    Hq, Hkv = q.shape[2], cache_k.shape[2]
    group = Hq // Hkv

    if q.shape[1] == 1 and window is None and _pallas_decode_enabled():
        from .attention_pallas import decode_gqa_attention

        out = decode_gqa_attention(
            q[:, 0],
            cache_k,
            cache_v,
            (q_positions[:, 0] + 1).astype(jnp.int32),
            interpret=jax.default_backend() != "tpu",
        )
        return out[:, None]

    # bf16 operands with fp32 accumulation: the MXU-native contraction. An
    # explicit .astype(f32) on the cache (the round-3 code) materializes
    # the WHOLE cache in fp32 every layer every step and pushes the matmul
    # off the bf16 fast path — measured ~2x slower decode chunks.
    # [B, T, Hkv, group, D] x [B, S, Hkv, D] -> [B, Hkv, group, T, S]
    qg = q.reshape(B, q.shape[1], Hkv, group, -1)
    scores = jnp.einsum("btkgd,bskd->bkgts", qg, cache_k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(q.shape[-1]))

    kv_pos = jnp.arange(S)[None, None, :]                # [1, 1, S]
    causal = kv_pos <= q_positions[:, :, None]           # [B, T, S]
    if window is not None:
        causal &= kv_pos > (q_positions[:, :, None] - window)
    mask = causal[:, None, None, :, :]                   # [B, 1, 1, T, S]
    scores = jnp.where(mask, scores, jnp.float32(-1e30))

    probs = jax.nn.softmax(scores, axis=-1)              # fp32
    out = jnp.einsum("bkgts,bskd->btkgd", probs.astype(cache_v.dtype),
                     cache_v, preferred_element_type=jnp.float32)
    return out.reshape(q.shape).astype(q.dtype)


def compose_prefix_lane(
    pool_k: jnp.ndarray,        # [L, P, ps, Hkv, D] prefix page pool
    pool_v: jnp.ndarray,
    prefix_table: jnp.ndarray,  # [Bp, PP] int32 page ids per row
    prefix_lens: jnp.ndarray,   # [Bp] int32 reused tokens per row
    sfx_k: jnp.ndarray,         # [L, Bp, T, Hkv, D] suffix K (stacked)
    sfx_v: jnp.ndarray,
    lane_pages: int,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Compose per-row KV LANE IMAGES for the dense-cache prefix path:
    lane[b, j] = reused prefix page content for j < prefix_lens[b], else
    the suffix K/V one-hot-placed at absolute position prefix_lens[b]+t.

    The one-hot einsum expresses per-row ragged placement with uniform
    shapes — per-row gather/scatter forms either serialize on TPU or take
    minutes to compile (see merge_chunk_kv). Entries beyond a row's
    prompt hold zeros/pad garbage, unreachable under the engine's
    write-before-read invariant. Returns [L, Bp, lane_pages*ps, Hkv, D]
    lane_k, lane_v.
    """
    L, P, ps = pool_k.shape[0], pool_k.shape[1], pool_k.shape[2]
    Bp, PP = prefix_table.shape
    T = sfx_k.shape[2]
    Pt = PP * ps
    lane_t = lane_pages * ps

    kp = pool_k[:, prefix_table].reshape((L, Bp, Pt) + pool_k.shape[3:])
    vp = pool_v[:, prefix_table].reshape((L, Bp, Pt) + pool_v.shape[3:])
    lane_j = jnp.arange(lane_t, dtype=jnp.int32)[None, :]
    in_prefix = (lane_j < prefix_lens[:, None])[None, :, :, None, None]
    sel = (lane_j[:, :, None]
           == (prefix_lens[:, None, None]
               + jnp.arange(T, dtype=jnp.int32)[None, None, :]))

    def lane(prefix, fresh):
        if lane_t > Pt:
            pad = jnp.zeros((L, Bp, lane_t - Pt) + prefix.shape[3:],
                            prefix.dtype)
            pre = jnp.concatenate([prefix, pad], axis=2)
        else:
            pre = prefix[:, :, :lane_t]
        suf = jnp.einsum("bjt,lbthd->lbjhd", sel.astype(fresh.dtype),
                         fresh, preferred_element_type=prefix.dtype)
        return jnp.where(in_prefix, pre, suf.astype(prefix.dtype))

    return lane(kp, sfx_k), lane(vp, sfx_v)


def gqa_attention_prefix(
    q: jnp.ndarray,          # [B, T, Hq, D] suffix queries
    prefix_k: jnp.ndarray,   # [B, Pt, Hkv, D] gathered prefix K (positions 0..)
    prefix_v: jnp.ndarray,
    suffix_k: jnp.ndarray,   # [B, T, Hkv, D] this call's K (current tokens)
    suffix_v: jnp.ndarray,
    prefix_lens: jnp.ndarray,  # [B] int32 — valid prefix length per row
    *,
    window: Optional[int] = None,
) -> jnp.ndarray:
    """Two-segment PREFILL attention for prefix-cache reuse: the suffix's
    queries attend a reused KV prefix (positions ``0..prefix_lens[b]``,
    gathered from the prefix page pool) plus the suffix itself causally.

    Row ``b``'s suffix token t sits at absolute position
    ``prefix_lens[b] + t``; the prefix segment is valid strictly below
    ``prefix_lens[b]`` (gather padding beyond a row's true prefix is
    masked). One fp32 softmax spans both segments — this is
    ``gqa_attention`` over the concatenated KV: because prefill attention
    reads the bf16-WRITTEN cache, the reused prefix K/V bytes are
    identical to a full recompute's, and only reduction tiling can differ
    (last-ulp). Returns [B, T, Hq, D] in q.dtype.

    No reference counterpart (the reference has no model/serving code);
    the vLLM-style automatic prefix caching pattern is noted in PAPERS.md.
    """
    B, T = q.shape[0], q.shape[1]
    Pt = prefix_k.shape[1]
    Hq, Hkv = q.shape[2], prefix_k.shape[2]
    group = Hq // Hkv
    D = q.shape[-1]

    qg = q.reshape(B, T, Hkv, group, D)
    s_p = jnp.einsum("btkgd,bskd->bkgts", qg, prefix_k,
                     preferred_element_type=jnp.float32)
    s_s = jnp.einsum("btkgd,bskd->bkgts", qg, suffix_k,
                     preferred_element_type=jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.float32(D))

    plen = prefix_lens[:, None, None]                    # [B, 1, 1]
    q_abs = prefix_lens[:, None] + jnp.arange(T)[None, :]  # [B, T]
    kv_pos = jnp.arange(Pt)[None, None, :]               # [1, 1, Pt]
    valid_p = kv_pos < plen                              # [B, 1→T, Pt]
    valid_p = jnp.broadcast_to(valid_p, (B, T, Pt))
    j = jnp.arange(T)[None, None, :]                     # [1, 1, T]
    valid_s = j <= jnp.arange(T)[None, :, None]          # [1, T, T] causal
    valid_s = jnp.broadcast_to(valid_s, (B, T, T))
    if window is not None:
        lo = q_abs[:, :, None] - window                  # [B, T, 1]
        valid_p &= kv_pos > lo
        valid_s &= (plen + j) > lo
    s_p = jnp.where(valid_p[:, None, None], s_p * scale, jnp.float32(-1e30))
    s_s = jnp.where(valid_s[:, None, None], s_s * scale, jnp.float32(-1e30))
    s = jnp.concatenate([s_p, s_s], axis=-1)             # [B, Hkv, g, T, Pt+T]
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgts,bskd->btkgd", p[..., :Pt].astype(prefix_v.dtype),
                     prefix_v, preferred_element_type=jnp.float32)
    out = out + jnp.einsum("bkgts,bskd->btkgd",
                           p[..., Pt:].astype(suffix_v.dtype), suffix_v,
                           preferred_element_type=jnp.float32)
    return out.reshape(q.shape).astype(q.dtype)


# --- kerncheck: interpreter-mode kernel sanitizer (obs/kerncheck.py) ----
# SWARMDB_KERNCHECK=1 swaps the TPU-gated dispatchers for checked
# wrappers. Every concrete (non-traced) ragged prefill call re-runs the
# kernel through the numpy grid interpreter with canary-poisoned outputs
# and bounds-checked Refs, then asserts parity against the dispatched
# result; every concrete chunked decode call is held to the XLA gather
# form (live rows in parity, exact zeros off the live-row list). Flag
# off, this block never runs and the module exports the plain function
# objects — type identity is pinned by tests/test_kernelcheck.py.
if os.environ.get("SWARMDB_KERNCHECK", "0") == "1":
    from ..obs.kerncheck import (checked_paged_attention_dispatch_chunked,
                                 checked_ragged_prefill_dispatch)

    paged_attention_dispatch_chunked = (
        checked_paged_attention_dispatch_chunked(
            paged_attention_dispatch_chunked))
    ragged_prefill_dispatch = checked_ragged_prefill_dispatch(
        ragged_prefill_dispatch)


# ------------------------------------------------------- latent (MLA) pages
#
# A latent configuration (models/deepseek.py) keeps ONE row a token a layer,
# ``[c_kv | k_pe]`` padded to the pool's width, with no heads axis and no
# values beside it: pages are ``[P, ps, Wd]``. Attention over them is in the
# ABSORBED form: a query head is ``[q_nope W_kvb^K | q_pe]`` times the
# softmax scale, as wide as a row, so every head scores against the same
# row, and the softmax-weighted sum of the rows themselves is the output
# (its first ``kv_lora_rank`` lanes; the caller takes them through
# ``W_kvb^V``). The dispatchers below follow the pattern of the GQA ones:
# the Pallas kernel on a TPU (pages read in place, once), the dense XLA
# form elsewhere.


def latent_kernels_enabled() -> bool:
    """Gate for BOTH latent kernels, decode and prefill: the kernels on a
    TPU and where SWARMDB_PALLAS=1 asks for them (interpret mode, tests),
    the dense XLA forms elsewhere. On a TPU the dense forms are refused
    by name: each gathers every row's pages dense a call, the gather of
    the pool that the decode step may never run (the engine asks here
    when it is built, so SWARMDB_PALLAS=0 fails there and not in a
    trace)."""
    enabled = _ragged_prefill_kernel_enabled()
    if not enabled and jax.default_backend() == "tpu":
        raise NotImplementedError(
            "latent pages are attended by the Pallas kernels on a TPU "
            "(mla_paged_decode_attention_chunked, "
            "mla_ragged_prefill_attention): with SWARMDB_PALLAS=0 the "
            "dense XLA forms would gather every row's pages out of the "
            "pool each step; they are for tests and CPU drives")
    return enabled



def latent_decode_attention_reference(
    q: jnp.ndarray,           # [B, Hq, Wd] absorbed, scaled queries
    pages: jnp.ndarray,       # [P, ps, Wd] FROZEN latent pool (or flat)
    page_table: jnp.ndarray,  # [B, maxp]
    chunk: jnp.ndarray,       # [B, Kc, Wd] this chunk's rows so far
    starts: jnp.ndarray,      # [B] frozen prefix length (chunk start)
    step: jnp.ndarray,        # scalar: index within the chunk
) -> jnp.ndarray:
    """Dense XLA form of ``mla_paged_decode_attention_chunked`` and its
    off-TPU fallback: gathers each row's pages. Returns [B, Hq, Wd]."""
    B, maxp = page_table.shape
    ps = pages.shape[1]
    rows = pages[page_table].reshape(B, maxp * ps, pages.shape[-1])
    keys = jnp.concatenate([rows, chunk.astype(rows.dtype)], axis=1)
    s = jnp.einsum("bhw,bkw->bhk", q, keys,
                   preferred_element_type=jnp.float32)
    valid = jnp.concatenate(
        [jnp.arange(maxp * ps, dtype=jnp.int32)[None] < starts[:, None],
         jnp.broadcast_to(jnp.arange(chunk.shape[1], dtype=jnp.int32)[None]
                          <= step, (B, chunk.shape[1]))], axis=1)
    p = jax.nn.softmax(jnp.where(valid[:, None], s, jnp.float32(-1e30)),
                       axis=-1)
    out = jnp.einsum("bhk,bkw->bhw", p.astype(keys.dtype), keys,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def latent_decode_dispatch(q, pages, page_table, chunk, starts, step):
    """Absorbed decode attention over the latent pool in place: each live
    page read once a call. [B, Hq, Wd]."""
    if latent_kernels_enabled():
        from .attention_pallas import mla_paged_decode_attention_chunked

        return mla_paged_decode_attention_chunked(
            q, pages, page_table, chunk, starts, step,
            interpret=jax.default_backend() != "tpu")
    return latent_decode_attention_reference(q, pages, page_table, chunk,
                                             starts, step)


def latent_prefill_attention_reference(
    q: jnp.ndarray,           # [W, Hq, Wd] absorbed, scaled query stream
    sfx: jnp.ndarray,         # [W, Wd] the wave's own rows, stream order
    pages: jnp.ndarray,       # [P, ps, Wd]
    row_tables: jnp.ndarray,  # [R, maxp]
    starts: jnp.ndarray,      # [R]
    lens: jnp.ndarray,        # [R]
    prefix_lens: jnp.ndarray,  # [R]
    tok_row: jnp.ndarray,     # [W]
) -> jnp.ndarray:
    """Dense XLA form of ``mla_ragged_prefill_attention``: every token
    against its own row's cached rows (gathered dense) and its row's
    earlier tokens of the stream, one softmax. Materializes [W, Pt, Wd]:
    for tests and CPU drives. Returns [W, Hq, Wd]."""
    W = q.shape[0]
    R, maxp = row_tables.shape
    ps = pages.shape[1]
    Pt = maxp * ps
    row = jnp.clip(tok_row, 0, R - 1)
    cached = pages[row_tables].reshape(R, Pt, pages.shape[-1])[row]
    sfx = sfx.astype(pages.dtype)
    s_p = jnp.einsum("whd,wpd->whp", q, cached,
                     preferred_element_type=jnp.float32)
    s_s = jnp.einsum("whd,xd->whx", q, sfx,
                     preferred_element_type=jnp.float32)
    x = jnp.arange(W, dtype=jnp.int32)
    valid_p = (jnp.arange(Pt, dtype=jnp.int32)[None]
               < prefix_lens[row][:, None])
    valid_s = (tok_row[:, None] == tok_row[None, :]) & (x[None] <= x[:, None])
    s = jnp.concatenate(
        [jnp.where(valid_p[:, None], s_p, jnp.float32(-1e30)),
         jnp.where(valid_s[:, None], s_s, jnp.float32(-1e30))], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("whp,wpd->whd", p[..., :Pt].astype(pages.dtype), cached,
                     preferred_element_type=jnp.float32)
    out = out + jnp.einsum("whx,xd->whd", p[..., Pt:].astype(sfx.dtype), sfx,
                           preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


def latent_prefill_dispatch(q, sfx, pages, row_tables, starts, lens,
                            prefix_lens, tok_row):
    """Absorbed attention of a packed wave over the latent pool in place
    and over the wave's own rows. [W, Hq, Wd]."""
    if latent_kernels_enabled():
        from .attention_pallas import mla_ragged_prefill_attention

        return mla_ragged_prefill_attention(
            q, sfx, pages, row_tables, starts, lens, prefix_lens,
            interpret=jax.default_backend() != "tpu")
    return latent_prefill_attention_reference(
        q, sfx, pages, row_tables, starts, lens, prefix_lens, tok_row)
