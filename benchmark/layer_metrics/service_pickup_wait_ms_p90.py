"""Service: how long a message lay published before the consumer took it
up, 90th percentile over the window's messages of the program's
``serve.pickup`` spans (the message's ``enqueued`` stamp -> the instant
``ServingService._consume_loop`` hands it to ``serve_message``), joined
to the window's rows by ``rid`` = message id. It lies before the engine's
submit, so it is in every ``ttft_p90_ms``. ``notes`` holds the median and
the mean, ``slept_share`` (the part of all that time the consumer's last
idle sleep of ``poll_interval`` covers: sum of ``slept_us`` over sum of
durations) and ``behind_mean`` (messages the same round served first). A
program without the span (an older commit) reads nothing."""
from benchmark.harness import spans
from benchmark.harness.stats import percentile

NAME = "service_pickup_wait_ms_p90"


def read(ctx):
    held = spans.engine_spans(ctx, NAME, cat="serving")
    if held is None:
        return None
    mids = {r["id"] for r in ctx["window_rows"] if r["id"]}
    picks = [e for e in held
             if e["name"] == "serve.pickup" and e["rid"] in mids]
    if not picks:
        return None
    ms = [e["dur_us"] * 1e-3 for e in picks]
    total_us = sum(e["dur_us"] for e in picks)
    ctx["notes"][NAME] = {
        "messages": len(picks), "p50": percentile(ms, 50),
        "mean": sum(ms) / len(ms),
        "slept_share": (sum(e["args"].get("slept_us", 0) for e in picks)
                        / total_us if total_us else 0.0),
        "behind_mean": (sum(e["args"].get("behind", 0) for e in picks)
                        / len(picks))}
    return percentile(ms, 90)
