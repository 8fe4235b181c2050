"""Arithmetic from a timeline to the end-to-end metrics. Kept with the
benchmark so that no PR that claims a gain can change it."""

from __future__ import annotations

import math
from typing import Dict, Iterable, List, Optional, Sequence


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Nearest-rank percentile (the smallest value with at least q% of the
    sample at or below it); ``None`` for an empty sample. No interpolation:
    a reported tail is a latency some message really had."""
    data = sorted(values)
    if not data:
        return None
    rank = max(1, math.ceil(q / 100.0 * len(data)))
    return data[min(rank, len(data)) - 1]


def tpot_s(first_token_t: float, last_token_t: float,
           n_tokens: int) -> Optional[float]:
    """Time per output token of one message: (last - first) / (n - 1).
    Tokens leave the engine in chunks, so single gaps are not the metric;
    a one-token reply has none."""
    if n_tokens < 2:
        return None
    return (last_token_t - first_token_t) / (n_tokens - 1)


def end_to_end(messages: Sequence[Dict], t0: float, seconds: float
               ) -> Dict[str, Optional[float]]:
    """The four end-to-end metrics from per-message records.

    A record holds ``due`` (absolute), and where it happened ``first_t``,
    ``last_t``, ``n_tokens``, ``reply_t``, ``done_t``. Only messages due
    in ``[t0, t0 + seconds)`` count; tails are over all of them that got
    that far, and the token rate is over messages whose engine request
    completed inside the window."""
    t1 = t0 + seconds
    win = [m for m in messages if t0 <= m["due"] < t1]
    reply = [(m["reply_t"] - m["due"]) * 1e3 for m in win
             if m.get("reply_t") is not None]
    ttft = [(m["first_t"] - m["due"]) * 1e3 for m in win
            if m.get("first_t") is not None]
    tpot = [t * 1e3 for t in (
        tpot_s(m["first_t"], m["last_t"], m["n_tokens"]) for m in win
        if m.get("first_t") is not None) if t is not None]
    done_tokens = sum(m["n_tokens"] for m in win
                      if m.get("done_t") is not None and m["done_t"] < t1)
    return {
        "reply_p90_ms": percentile(reply, 90),
        "ttft_p90_ms": percentile(ttft, 90),
        "tpot_p90_ms": percentile(tpot, 90),
        "out_tokens_per_s": done_tokens / seconds if seconds > 0 else None,
    }


def histogram_quantile(boundaries: List[float], counts: List[int],
                       q: float) -> Optional[float]:
    """Quantile of a fixed-bucket histogram (per-bucket counts, the last
    one the overflow bucket), interpolated linearly inside the bucket."""
    total = sum(counts)
    if total <= 0:
        return None
    target = q / 100.0 * total
    seen = 0.0
    for i, c in enumerate(counts):
        if c and seen + c >= target:
            lo = boundaries[i - 1] if i > 0 else 0.0
            hi = boundaries[i] if i < len(boundaries) else boundaries[-1]
            return lo + (hi - lo) * (target - seen) / c
        seen += c
    return boundaries[-1]
