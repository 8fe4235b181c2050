"""Service: what ``serve_message`` takes on the consumer's thread, median
over the window's messages of the program's ``serve.request`` spans
(entry -> the engine's submit returned), joined to the window's rows by
``rid`` = message id. It lies before the engine's submit, so it is in
every ``ttft_p90_ms``. ``notes`` holds its two parts at the 50th and the
90th percentile, ``build_us`` (the history read: prompt build, rolling
plan, trim) and ``submit_us`` (the submit alone: the engine's lock), and
the mean ``prompt_tokens``; a program whose span has no such arguments
(an older commit) leaves them ``None`` there."""
from benchmark.harness import spans
from benchmark.harness.stats import percentile

NAME = "service_request_ms_p50"


def read(ctx):
    held = spans.engine_spans(ctx, NAME, cat="serving")
    if held is None:
        return None
    mids = {r["id"] for r in ctx["window_rows"] if r["id"]}
    reqs = [e for e in held
            if e["name"] == "serve.request" and e["rid"] in mids]
    if not reqs:
        return None
    note = {"messages": len(reqs)}
    for part in ("build_us", "submit_us"):
        vals = [e["args"][part] for e in reqs if part in e["args"]]
        note[part] = {"p50": percentile(vals, 50),
                      "p90": percentile(vals, 90)}
    toks = [e["args"]["prompt_tokens"] for e in reqs
            if "prompt_tokens" in e["args"]]
    note["prompt_tokens_mean"] = sum(toks) / len(toks) if toks else None
    ctx["notes"][NAME] = note
    return percentile([e["dur_us"] * 1e-3 for e in reqs], 50)
