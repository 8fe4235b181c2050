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

What PR 29 read against this (``PERF.md`` section 6 and 7). The largest
gap of a sample separates a broken kernel or sampler from a sound run, and
hardly a lower precision: int8 pages read at most 0.048 at the tests' tiny
widths, and int8-rounded keys and values 0.064-0.125 against a sound
0.032-0.054 at hidden 4096, so the sentence above on int8 states an intent
that the readings do not bear.

An architecture that chooses (experts by a router) is held on the
reference FORCED to the program's own choices (PR 35). Unforced the rule
is not steady: at a near tie a correct bf16 program chooses otherwise than
the float32 reference and a logit jumps by 0.1-2 with nothing wrong. The
program reports what it chose (``GenRequest.routing``, kept by the
harness's ``Recorder``), and a reference module that declares
``FOLLOWS_ROUTING = True`` takes those rows as a fifth argument and
computes, in float32, the experts the program computed, with its own
gates there: the comparison then reads as a dense stack's does (at most
0.031 on the CPU and 0.032 on the chip, where the same records read up to
2.81 unforced and a broken sampler at least 0.50 forced, ``PERF.md``
section 6), and LOGIT_TOL is the same 0.1. A record
that reaches such a reference without a row for every position that went
through the stack cannot be followed, and the check raises on it and
never falls back to the unforced comparison. What following cannot see: a
router whose own arithmetic is wrong and still reports what it then
computed (``PERF.md`` section 4 says what covers that). A reference
without the attribute is called with four arguments, as before."""

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


def follows_routing(reference) -> bool:
    """Whether the reference module asks for the program's routing."""
    return bool(getattr(reference, "FOLLOWS_ROUTING", False))


def routing_rows(rec: Dict[str, Any], need: int, T: int):
    """The record's routing for a following reference: its first ``need``
    rows (one for every position whose output was read: all of prompt +
    compared tokens but the last), padded to ``T`` with the row that marks
    every choice as left out (``~0``), so that a padded token takes
    nothing and, the stack being causal, no compared position reads it."""
    import numpy as np

    routing = rec["routing"]
    if routing is None or not rec["routing_complete"]:
        raise ValueError("a record without complete routing reached a "
                         "reference that follows it")
    if len(routing) < need:
        raise ValueError(f"{len(routing)} routing rows for a sequence "
                         f"that needs {need}")
    rows = np.full((T,) + tuple(routing.shape[1:]), ~0, np.int16)
    rows[:need] = routing[:need]
    return rows


def logit_gaps(stack, records: List[Dict[str, Any]], reference
               ) -> List[float]:
    """For each record the largest (reference maximum - reference logit of
    the engine's token) over its generated positions. ``reference`` is the
    module the cell's configuration names; its dimensions come from the
    configuration file through its own ``dims``, never from the program's
    configuration: a wrong mapping of the file onto the program would
    otherwise be wrong on both sides and pass. A reference that
    ``follows_routing`` is also handed the record's ``routing_rows``."""
    import jax.numpy as jnp
    import numpy as np

    dims = reference.dims(stack.cfg_file)
    params = stack.lanes[0].params
    follows = follows_routing(reference)
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
        followed = ((jnp.asarray(routing_rows(rec, len(seq) - 1, T)),)
                    if follows else ())
        logits = reference.logits_at(params, dims, jnp.asarray(toks),
                                     jnp.asarray(at), *followed)
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
