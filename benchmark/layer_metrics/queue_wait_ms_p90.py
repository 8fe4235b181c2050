"""Scheduler: the engine's own ``queue_wait_s`` latencies (submit ->
admission into a slot), those observed during the window, 90th
percentile."""
from benchmark.harness.stats import percentile


def read(ctx):
    p = percentile(ctx["queue_wait_s"], 90)
    return None if p is None else p * 1e3
