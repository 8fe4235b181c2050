"""The comparison that decides ``correct``.

Tokens cannot be compared: the weights are random, so two correct
implementations flip the argmax wherever the top two logits are closer
than their rounding noise. Logits can. For every generated token of a
sampled request the reference's logit of the token the engine chose must
lie within LOGIT_TOL of the reference's maximum at that position.

LOGIT_TOL = 0.1. The engine multiplies in bf16 (Pallas online softmax over
pages, chunked decode against a paged pool), the reference in float32.
Logits here are ~N(0, 1) with a maximum near 4-5 over 32k-64k entries; one
bf16 rounding of such a value is up to 0.02. The widest gap read on the
chip is 0.037 (28 requests of seven traced runs, contexts to 3.7k tokens,
PR 24; ``chip_smoke.py`` read under 0.03 in PR 22), and the tolerance is
about three times that. Near the maximum, neighbouring logits lie about
0.2 apart, so a path that adds 0.1-0.2 of noise to a logit (int8 or fp8
pages, a bf16 accumulator) is meant to fail here: that is another result,
and a benchmark PR decides its tolerance and says why. A kernel that
mis-tiles or mis-masks moves the chosen token to a typical logit, 4-5
below the maximum.

What PR 29 read against this (``PERF.md`` section 6 and 7; nothing here
changed for it). The largest gap of a sample separates a broken kernel or
sampler from a sound run, and hardly a lower precision: int8 pages read
at most 0.048 at the tests' tiny widths, and int8-rounded keys and values
0.064-0.125 against a sound 0.032-0.054 at hidden 4096, so the sentence
above on int8 states an intent that the readings do not bear. Under an
architecture that chooses (experts by a router) the rule is not steady
either: at a near tie a correct bf16 program chooses otherwise than the
float32 reference and a logit jumps by 0.1-2 with nothing wrong, so such
a configuration is first held when the program reports its choices and
its reference follows them."""

from __future__ import annotations

import random
from typing import Any, Dict, List

LOGIT_TOL = 0.1
MAX_AT = 256          # generated positions checked per request (padded to)


def sample(records: List[Dict[str, Any]], seed: int, n: int
           ) -> List[Dict[str, Any]]:
    """The longest finished request and ``n - 1`` others drawn from the
    seed."""
    done = [r for r in records if r["tokens"]]
    if not done:
        return []
    done.sort(key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    longest, rest = done[-1], done[:-1]
    random.Random(f"check:{seed}").shuffle(rest)
    return [longest] + rest[:max(0, n - 1)]


def logit_gaps(stack, records: List[Dict[str, Any]], reference
               ) -> List[float]:
    """For each record the largest (reference maximum - reference logit of
    the engine's token) over its generated positions. ``reference`` is the
    module the cell's configuration names; its dimensions come from the
    configuration file through its own ``dims``, never from the program's
    configuration: a wrong mapping of the file onto the program would
    otherwise be wrong on both sides and pass."""
    import jax.numpy as jnp
    import numpy as np

    dims = reference.dims(stack.cfg_file)
    params = stack.lanes[0].params
    gaps = []
    for rec in records:
        if rec["resume_len"]:
            raise ValueError("a rolling resume reached the check")
        p, g = rec["prompt"], rec["tokens"][:MAX_AT]
        seq = p + g
        T = -(-len(seq) // reference.Q_BLOCK) * reference.Q_BLOCK
        toks = np.zeros((T,), np.int32)
        toks[:len(seq)] = seq
        at = np.zeros((MAX_AT,), np.int32)
        # position len(p) - 1 + i predicts g[i]
        at[:len(g)] = np.arange(len(p) - 1, len(p) - 1 + len(g))
        logits = reference.logits_at(params, dims, jnp.asarray(toks),
                                     jnp.asarray(at))
        logits = np.asarray(logits)[:len(g)]
        if not np.isfinite(logits).all():
            raise ValueError("non-finite reference logits")
        chosen = logits[np.arange(len(g)), np.asarray(g)]
        gaps.append(float((logits.max(axis=-1) - chosen).max()))
    return gaps


def replies_ok(rows: List[Dict[str, Any]]) -> List[str]:
    """Faults of the window's messages, as text; empty when all is well.
    Every message has exactly one reply, and a reply holds its
    ``max_new_tokens`` unless the model ended it."""
    faults = []
    for r in rows:
        if r["replies"] != 1:
            faults.append(f"{r['id']}: {r['replies']} replies")
        elif (r["completion_tokens"] != r["max_new"]
              and r["finish_reason"] != "eos"):
            faults.append(f"{r['id']}: {r['completion_tokens']} of "
                          f"{r['max_new']} tokens, {r['finish_reason']}")
    return faults
