"""Service: from the engine's ``on_done`` to the reply sent and the
message marked processed, median over the window's messages of
``queued_us`` (the wait in the service's reply queue) plus the duration
of the program's ``serve.reply`` span (the reply thread took the item ->
``_emit_reply`` done, its last attempt's end where it retried), joined
to the window's rows by ``rid`` = message id. It lies behind the last
token, so it is in ``reply_p90_ms`` alone. ``notes`` holds the medians of
the four parts (``queued_us``, ``decode_us``, ``send_us``, and the rest of
the span: marking processed, the counters) and the most ``attempts`` an
emit took. A program without the span (an older commit) reads nothing."""
from benchmark.harness import spans
from benchmark.harness.stats import percentile

NAME = "service_reply_ms_p50"


def read(ctx):
    held = spans.engine_spans(ctx, NAME, cat="serving")
    if held is None:
        return None
    mids = {r["id"] for r in ctx["window_rows"] if r["id"]}
    reps = [e for e in held
            if e["name"] == "serve.reply" and e["rid"] in mids]
    if not reps:
        return None
    parts = {k: [e["args"].get(k, 0) for e in reps]
             for k in ("queued_us", "decode_us", "send_us")}
    parts["rest_us"] = [e["dur_us"] - d - s for e, d, s in
                        zip(reps, parts["decode_us"], parts["send_us"])]
    ctx["notes"][NAME] = {
        "messages": len(reps),
        "p50_us": {k: percentile(v, 50) for k, v in parts.items()},
        "attempts_max": max(e["args"].get("attempts", 1) for e in reps)}
    return percentile([(q + e["dur_us"]) * 1e-3
                       for e, q in zip(reps, parts["queued_us"])], 50)
