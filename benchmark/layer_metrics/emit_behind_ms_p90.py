"""Scheduler: how far behind the device the engine thread emits, 90th
percentile over the window's ``engine.emit`` phases of ``behind_us``:
from the resident callback's stamp (the chunk reached the host and the
vote was taken) to the moment the engine thread begins to process the
block. It is what the hand-off adds to a token's way out; the chunk after
it is running on the device meanwhile. A program whose callback does the
emitting itself writes no ``behind_us`` and reads nothing."""
from benchmark.harness import spans
from benchmark.harness.stats import percentile

NAME = "emit_behind_ms_p90"


def read(ctx):
    held = spans.engine_spans(ctx, NAME)
    if held is None:
        return None
    behind = [e["args"]["behind_us"] * 1e-3 for e in held
              if e["name"] == "engine.emit" and "behind_us" in e["args"]
              and spans.in_window(ctx, e["end_s"])]
    return percentile(behind, 90)
