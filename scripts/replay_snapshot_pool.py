#!/usr/bin/env python3
"""Replay a chat cell's plan against a pool of state snapshots, on the CPU.

    python3 scripts/replay_snapshot_pool.py --slots 99 115 135 --seeds 1 2 3

For each seed the plan of ``benchmark/traffic/gen_sessions.py`` under
``chat`` with the cell's three keys (``--traffic``), walked in arrival
order: a turn after a conversation's first resumes from the conversation's
snapshot if the pool still holds it, else it is forgone (its matched
tokens are computed again), and then takes a snapshot at its own prompt's
last page end by ``--rule``: ``shallowest`` (``PrefixLRU.take_state_slot``:
the shallowest leaves, and only for a deeper newcomer), ``lru`` or
``newest``. Prints, a pool size, the forgone share of matched tokens and
the forgone turns of the window's messages, a seed. A reply is taken at
its ``max_new_tokens`` and a line's overhead at 12 tokens, so the shares
are the chip's to a few points (PERF.md section 6, PR 50: 11.3-22.4
replayed where the chip read 10.9-22.1 on the same twelve seeds). No
device, no engine: what it sizes is the pool, not the time.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.traffic import gen_sessions  # noqa: E402

PAGE, LINE = 16, 12


def replay(params, seed, seconds, slots, rule):
    """``(forgone share of matched tokens in %, forgone turns)`` over the
    window's messages."""
    held = {}                     # conversation -> (depth in tokens, due)
    prompt_of, history = {}, {}
    forgone = reused = turns = 0
    for a in gen_sessions.plan(params, seed, seconds)["arrivals"]:
        who, now = a["sender"], a["due"]
        prompt = history.get(who, 0) + len(a["text"]) + LINE
        matched = prompt_of.get(who, 0) // PAGE * PAGE
        resumed = matched and who in held
        if matched and a["phase"] == "window":
            if resumed:
                reused += matched
            else:
                forgone += matched
                turns += 1
        depth = prompt // PAGE * PAGE
        if not resumed and len(held) >= slots:
            by = {"shallowest": lambda c: held[c][0],
                  "lru": lambda c: held[c][1],
                  "newest": lambda c: -held[c][1]}[rule]
            out = min(held, key=by)
            if rule != "shallowest" or held[out][0] < depth:
                del held[out]
        if resumed or len(held) < slots:
            held[who] = (depth, now)
        prompt_of[who] = prompt
        history[who] = prompt + a["max_new_tokens"] + LINE
    return 100.0 * forgone / max(1, forgone + reused), turns


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--traffic",
                    default="benchmark/traffic/chat-nemotron3.json")
    ap.add_argument("--slots", type=int, nargs="+", default=[99])
    ap.add_argument("--seeds", type=int, nargs="+", default=[1, 2, 3, 4, 5, 6])
    ap.add_argument("--rule", default="shallowest",
                    choices=("shallowest", "lru", "newest"))
    ap.add_argument("--seconds", type=float, default=50.0)
    args = ap.parse_args()
    mine = json.load(open(os.path.join(ROOT, args.traffic)))
    params = json.load(open(os.path.join(
        ROOT, "benchmark/traffic", mine.pop("base") + ".json")))
    params.update(mine)
    for slots in args.slots:
        rows = [replay(params, s, args.seconds, slots, args.rule)
                for s in args.seeds]
        print(json.dumps({"slots": slots, "rule": args.rule,
                          "forgone_share": [round(r[0], 1) for r in rows],
                          "forgone_turns": [r[1] for r in rows]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
