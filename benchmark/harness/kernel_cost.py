"""Operations and bytes the two attention kernels need, from shapes alone
(never from ``cost_analysis()``). Bytes are the least traffic the
algorithm allows: every query, key, value and output element moves once.

Shapes: ``hq`` query heads, ``hkv`` KV heads, ``hd`` head size,
``itemsize`` bytes an element (2 for bf16)."""

from __future__ import annotations

from typing import Iterable, Tuple


def ragged_prefill_attention(rows: Iterable[Tuple[int, int]], hq: int,
                             hkv: int, hd: int, itemsize: int = 2):
    """One ragged prefill wave. ``rows``: (prefix_len, new_len) per row:
    ``new_len`` new tokens attend to ``prefix_len`` cached tokens read in
    place from the pool and causally to themselves. Returns (flops, bytes).

    flops: QK^T and PV are each 2 * hd a (query, key) pair a head; a row
    has new * prefix + new * (new + 1) / 2 pairs.
    bytes: Q in and O out for the new tokens, K and V for prefix + new."""
    flops = bytes_moved = 0
    for prefix, new in rows:
        pairs = new * prefix + new * (new + 1) // 2
        flops += 4 * hd * hq * pairs
        bytes_moved += itemsize * hd * (2 * hq * new + 2 * hkv * (prefix + new))
    return flops, bytes_moved


def paged_decode_attention(context_lens: Iterable[int], hq: int, hkv: int,
                           hd: int, itemsize: int = 2):
    """One decode step over a batch. ``context_lens``: tokens each live
    slot attends to (its one query included). Returns (flops, bytes)."""
    flops = bytes_moved = 0
    for ctx in context_lens:
        flops += 4 * hd * hq * ctx
        bytes_moved += itemsize * hd * (2 * hq + 2 * hkv * ctx)
    return flops, bytes_moved
