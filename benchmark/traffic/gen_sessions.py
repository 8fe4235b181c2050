"""Generator ``sessions``: conversations of one or more turns.

``live_conversations`` conversations are open at any time, each between
one user agent and one of ``assistants`` LLM-backed agents. Messages
arrive open loop at ``rate_per_s``, with exponential gaps stratified as
``harness/draws.py`` says. An agent speaks again only after it has read
the reply, so each message goes to a conversation whose previous turn was
due at least ``turn_gap_s`` earlier: the time a reply may take plus the
time to read it (the least recent conversation if none is that old). The
choice is made here, from the seed and the schedule alone, never from
what the system has answered, so the same seed sends the same messages
whatever the system does; the run counts the turns that were due before
the previous reply was in (``turns_before_reply`` among its facts). A
conversation ends after its drawn number of turns and a fresh user agent
takes its place: with ``turns`` fixed at 1 every message comes from an
agent with no history, and nothing is shared."""

from __future__ import annotations

import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import draws  # noqa: E402


def plan(params, seed, seconds):
    rng = random.Random(f"sessions:{seed}")
    rate = params["rate_per_s"]
    block = int(params.get("block", 0))
    assistants = [f"assistant-{i}" for i in range(params["assistants"])]
    turn_pool = draws.grid(params["turns"], 64, rng)
    n_users = 0

    def fresh():
        nonlocal n_users
        conv = {"user": f"user-{n_users}", "assistant": rng.choice(assistants),
                "turns": turn_pool[n_users % len(turn_pool)],
                "last": float("-inf"), "said": 0}
        n_users += 1
        return conv

    live = [fresh() for _ in range(params["live_conversations"])]
    arrivals = []
    start = -float(params["warm_s"])
    for phase, dur in (("warm", params["warm_s"]), ("window", seconds),
                       ("cool", params["cool_s"])):
        n = max(1, round(rate * dur)) if dur > 0 else 0
        if n == 0:
            continue
        offsets = draws.gap_offsets(n, dur, rng, block)
        chars = draws.grid(params["user_chars"], n, rng, block=block)
        new = draws.grid(params["max_new_tokens"], n, rng, block=block)
        for i in range(n):
            due = start + offsets[i]
            idle = [c for c in live
                    if due - c["last"] >= params["turn_gap_s"]]
            conv = (rng.choice(idle) if idle
                    else min(live, key=lambda c: c["last"]))
            conv["last"] = due
            conv["said"] += 1
            arrivals.append({
                "due": due, "phase": phase, "sender": conv["user"],
                "receiver": conv["assistant"],
                "text": draws.text(chars[i],
                                   f"{seed}:{conv['user']}:{conv['said']}"),
                "max_new_tokens": new[i]})
            if conv["said"] >= conv["turns"]:
                live[live.index(conv)] = fresh()
        start += dur
    return {"assistants": assistants, "arrivals": arrivals}
