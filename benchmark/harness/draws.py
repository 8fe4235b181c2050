"""Seeded draws that give every seed the same work in another order.

A distribution is sampled on its quantile grid (the i-th of n values is
the quantile at (i + 0.5) / n) and the grid is ordered by the seed, in
blocks that each hold the same spread of values. Two seeds therefore send
the same multiset of sizes and gaps, the same in every stretch of the
window, and differ only in which message gets which: a difference between
seeds is then noise of the system, not a different amount of work.

This is a stratified stream, not a Poisson one: the count of arrivals is
fixed, the gaps are the exponential distribution's quantiles, and a burst
or a lull is at most one block long. What a real Poisson stream's longer
bursts do to the tails is for a cell with a burst schedule of its own."""

from __future__ import annotations

import math
import random
from statistics import NormalDist
from typing import Any, Dict, List

_WORDS = ("agent broker swarm message route reply token stream cache page "
          "prefix engine batch slot queue partition offset topic consumer "
          "ledger memory window digest answer question report summary plan "
          "task tool result status error retry leader replica follower "
          "commit index vector search update delete insert merge split "
          "shard lane device kernel matrix tensor layer model prompt").split()


def quantile(dist: Dict[str, Any], u: float) -> float:
    """Inverse CDF of a distribution given as data (``dist`` names it)."""
    kind = dist["dist"]
    if kind == "lognormal":
        x = dist["median"] * math.exp(
            dist["sigma"] * NormalDist().inv_cdf(u))
    elif kind == "uniform":
        x = dist["min"] + (dist["max"] - dist["min"]) * u
    elif kind == "geometric":       # support 1, 2, ...; mean = 1 / p
        p = 1.0 / dist["mean"]
        x = max(1.0, math.ceil(math.log(1.0 - u) / math.log(1.0 - p)))
    elif kind == "exponential":
        x = -math.log(1.0 - u) * dist["mean"]
    elif kind == "fixed":
        x = dist["value"]
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    lo, hi = dist.get("min"), dist.get("max")
    if lo is not None:
        x = max(lo, x)
    if hi is not None:
        x = min(hi, x)
    return x


def _blocked(vals: List[float], block: int, rng: random.Random
             ) -> List[float]:
    """Order ``vals`` (sorted) for sending. With ``block`` = 0 one shuffle
    of all of them. Otherwise in blocks of about ``block`` values: the
    sorted values are dealt out over the blocks back and forth, so each
    block holds the same spread of small and large values, and only the order
    inside a block comes from the seed. Every stretch of the window then
    carries the same work for every seed."""
    k = max(1, round(len(vals) / block)) if block else 1
    parts: List[List[float]] = [[] for _ in range(k)]
    for rank, v in enumerate(vals):
        row, col = divmod(rank, k)
        # back and forth over the blocks, so that their sums agree
        parts[col if row % 2 == 0 else k - 1 - col].append(v)
    out: List[float] = []
    for part in parts:
        rng.shuffle(part)
        out.extend(part)
    return out


def grid(dist: Dict[str, Any], n: int, rng: random.Random,
         integer: bool = True, block: int = 0) -> List[float]:
    """``n`` values on the quantile grid of ``dist``, ordered by ``rng``
    (see ``_blocked``)."""
    vals = [quantile(dist, (i + 0.5) / n) for i in range(n)]
    if integer:
        vals = [int(round(v)) for v in vals]
    return _blocked(vals, block, rng)


def gap_offsets(n: int, seconds: float, rng: random.Random,
                block: int = 0) -> List[float]:
    """Arrival offsets of ``n`` messages in ``[0, seconds)``: exponential
    gaps on their quantile grid, scaled to sum to ``seconds`` and ordered
    as ``grid`` orders. The first arrival is at 0; the last gap ends the
    span."""
    gaps = grid({"dist": "exponential", "mean": 1.0}, n, rng, integer=False,
                block=block)
    scale = seconds / sum(gaps)
    out, t = [], 0.0
    for g in gaps:
        out.append(t)
        t += g * scale
    return out


def text(n_chars: int, salt: str) -> str:
    """Exactly ``n_chars`` characters of seeded words, unique per salt
    (one character is one token under the byte tokenizer)."""
    rng = random.Random(salt)
    parts, size = [f"[{salt}]"], len(salt) + 2
    while size < n_chars:
        w = rng.choice(_WORDS)
        parts.append(w)
        size += len(w) + 1
    return " ".join(parts)[:n_chars].ljust(n_chars, ".")
