"""Process: how late a thread that only sleeps wakes, 90th percentile
over the window's ``process.sample`` spans of each one's ``late_ms_max``
(``swarmdb_tpu/obs/procwatch.py``: the watcher sleeps to deadlines 20 ms
apart and a sample holds the latest of five wakes; the samples a stall
forces are among them). The wait for a CPU plus the wait for the
interpreter: what each thread on a message's path pays at every hop."""
from benchmark.harness import spans
from benchmark.harness.stats import percentile

NAME = "watch_wake_late_ms_p90"


def read(ctx):
    held = spans.engine_spans(ctx, NAME, cat="process")
    if not held:
        return None
    late = [e["args"]["late_ms_max"] for e in held
            if e["name"] == "process.sample"
            and spans.in_window(ctx, e["end_s"])
            and "late_ms_max" in e["args"]]
    return percentile(late, 90)
