"""Scheduler: admission -> first token inside the engine, 90th percentile
over the window's messages. Reads the program's ``engine.first_token``
spans (prefill start of the request's admission round -> its first token
handed to the service: the prefill waves, then the whole decode chunk the
token leaves with), joined to the window's messages by ``args["mid"]``.
The stretch of ``ttft_p90_ms`` that ``queue_wait_ms_p90`` ends before."""
from benchmark.harness import spans
from benchmark.harness.stats import percentile

NAME = "first_token_after_admit_ms_p90"


def read(ctx):
    held = spans.engine_spans(ctx, NAME)
    if held is None:
        return None
    mids = {r["id"] for r in ctx["window_rows"] if r["id"]}
    return percentile([e["dur_us"] * 1e-3 for e in held
                       if e["name"] == "engine.first_token"
                       and e["args"].get("mid") in mids], 90)
