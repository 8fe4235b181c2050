"""Service: what ``backend/service.py`` adds round the engine, by message
id: (engine submit - stage ``enqueued``) + (reply read from the inbox -
engine ``on_done``). Median over the window's messages."""
from benchmark.harness.stats import percentile


def read(ctx):
    vals = []
    for r in ctx["window_rows"]:
        enq = r["stages"].get("enqueued")
        if None in (enq, r["submit_t"], r["done_t"], r["reply_t"]):
            continue
        vals.append(((r["submit_t"] - enq) + (r["reply_t"] - r["done_t"]))
                    * 1e3)
    return percentile(vals, 50)
