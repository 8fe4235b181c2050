"""Operations and bytes the two attention kernels need, from shapes alone
(never from ``cost_analysis()``). Bytes are the least traffic the
algorithm allows: every query, key, value and output element moves once.

Shapes: ``hq`` query heads, ``hkv`` KV heads, ``hd`` head size,
``itemsize`` bytes an element (2 for bf16)."""

from __future__ import annotations

from typing import Any, Dict, Iterable, Tuple

# entries of a published ``layer_types`` whose layer runs an attention kernel
ATTENTION_LAYERS = ("full_attention", "sliding_attention")


def attending_layers(cfg_file: Dict[str, Any]) -> int:
    """How many of the configuration's layers call an attention kernel:
    of the ``num_hidden_layers`` that run, the entries of the file's
    published ``layer_types`` that name attention (a layer of another
    kind, a convolution or a recurrence, calls none), and every layer
    where the file has no such key."""
    n = cfg_file["num_hidden_layers"]
    kinds = cfg_file.get("layer_types")
    if kinds is None:
        return n
    return sum(kind in ATTENTION_LAYERS for kind in kinds[:n])


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
