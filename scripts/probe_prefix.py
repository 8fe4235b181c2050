#!/usr/bin/env python
"""Round-4: decompose serve-mode prefix-cache misses (S=256 plateau).

Logs every Engine._prefix_plan call as (prompt_len, matched_tokens) and
groups admissions by ANCHOR (hash of the prompt's first page): within a
group, consecutive prompts should be prefix-extensions, so matched should
track the previous admission's full pages. Prints the shortfall
distribution for repeat-anchor admissions plus anchor-churn stats.

Run: SWARMDB_BENCH_MODEL=tiny-debug python scripts/probe_prefix.py
"""
import collections
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("SWARMDB_BENCH_MODEL", "tiny-debug")
seconds = float(os.environ.get("PROBE_SECONDS", "60"))

import bench  # noqa: E402

bench._force_cpu()  # a CPU probe by design: counts, not device time

from swarmdb_tpu.backend.engine import Engine  # noqa: E402

model = os.environ.get("SWARMDB_BENCH_MODEL")
n_users = int(os.environ.get("SWARMDB_BENCH_AGENTS", "40"))
n_assistants = int(os.environ.get("SWARMDB_BENCH_ASSISTANTS", "4"))
max_batch = int(os.environ.get("SWARMDB_BENCH_BATCH", "16"))
max_seq = int(os.environ.get("SWARMDB_BENCH_SEQ", "256"))

samples = []
_plan_orig = Engine._prefix_plan


def plan_logged(self, prompt, pin=False):
    hits, chains = _plan_orig(self, prompt, pin)
    ps = self._prefix_ps
    samples.append((hash(tuple(prompt[:ps])), len(prompt),
                    len(hits) * ps))
    return hits, chains


Engine._prefix_plan = plan_logged

with bench.serving_stack(model, n_assistants, max_batch, max_seq,
                         16) as (db, service, assistants):
    users = [f"user_{i}" for i in range(n_users)]
    for u in users:
        db.register_agent(u)
    gen = {"generation": {"max_new_tokens": 16, "temperature": 0.0}}

    def send(i):
        db.send_message(users[i % n_users], assistants[i % n_assistants],
                        f"Hello #{i}, what is the plan?",
                        metadata=dict(gen))

    pump = bench._make_pump(db, max_batch * 2, send)
    pump(time.time() + seconds)
    pool = service.engine._prefix.stats()

ps = 16
groups = collections.Counter()
last_len = {}
events = collections.Counter()
tok = collections.Counter()
shortfalls = collections.Counter()
total = 0
for anchor, n, m in samples:
    total += n
    n_full = (n // ps) * ps
    cacheable = max(0, n_full - ps)
    first = anchor not in last_len
    groups[anchor] += 1
    prev = last_len.get(anchor)
    last_len[anchor] = n
    if first:
        events["anchor_first_seen"] += 1
        tok["anchor_first_seen"] += n
        continue
    events["repeat"] += 1
    gap = cacheable - m
    if m == 0:
        events["repeat_zero_match"] += 1
        tok["repeat_zero_match"] += n
    else:
        tok["repeat_suffix"] += n - m
        shortfalls[min(gap // ps, 8)] += 1
        if gap > 0:
            events["repeat_partial"] += 1
            tok["repeat_shortfall"] += gap
        else:
            events["repeat_full"] += 1

hit_tok = pool["hit_tokens"]
print(f"admissions={len(samples)} anchors={len(groups)} "
      f"users={n_users} prompt_tokens={total}")
print(f"pool={pool}")
print(f"plan hit rate = {sum(m for _, _, m in samples)/max(1,total):.1%}")
for k, v in events.most_common():
    print(f"  {k:22s} {v:6d}")
for k, v in tok.most_common():
    print(f"  tokens[{k}]  {v:8d} ({v/max(1,total):.1%})")
print("  shortfall pages histogram (repeat, matched>0):",
      dict(sorted(shortfalls.items())))
reps = sorted(groups.values(), reverse=True)
print(f"  admissions per anchor: top={reps[:8]} "
      f"singleton_anchors={sum(1 for v in reps if v == 1)}")
