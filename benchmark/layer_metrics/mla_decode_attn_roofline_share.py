"""Kernels: the latent (MLA) paged decode attention kernel against its
roofline over the traced span: least time by the table of peaks for the
work the kernel had, over the kernel's time in the trace
(``mla_paged_decode_attention_chunked`` events, named after the kernel's
function in ``ops/attention_pallas.py``).

The work is the absorbed form's (``harness/mla_cost.py``), from the
configuration file's published keys and the harness's own records: a
request decodes one token a step between its first and its last token, at
a context of its prompt plus what it has generated so far, in every layer.
The part of each request that falls into the span is taken in proportion
to time. A program without the kernel (an older commit, a configuration
without latent pages) reads nothing."""

from benchmark.harness import mla_cost, peaks

NAME = "mla_decode_attn_roofline_share"
KERNEL = "mla_paged_decode_attention_chunked"


def row_steps(ctx):
    """``[(steps taken inside the traced span, mean context there)]`` a
    request of the harness's records."""
    t0, t1 = ctx["trace_span"]
    out = []
    for rec in ctx["engine_records"].values():
        a, b, n = rec["first_t"], rec["last_t"], rec["n_tokens"]
        if a is None or n < 2 or b <= a:
            continue
        lo, hi = max(a, t0), min(b, t1)
        if hi <= lo:
            continue
        out.append(((n - 1) * (hi - lo) / (b - a),
                    len(rec["prompt"]) + n * ((lo + hi) / 2 - a) / (b - a)))
    return out


def read(ctx):
    tr = ctx["trace"]
    k = tr and tr["kernels"].get(KERNEL)
    if not k or k["seconds"] <= 0 or "kv_lora_rank" not in ctx["config"]:
        return None
    cfg = ctx["config"]
    flops = moved = 0.0
    for steps, context in row_steps(ctx):
        f, by = mla_cost.absorbed_decode(cfg, [context])
        flops += steps * f * cfg["num_hidden_layers"]
        moved += steps * by * cfg["num_hidden_layers"]
    if not moved:
        return None
    least, bound = peaks.least_seconds(flops, moved, ctx["device_kind"])
    ctx["notes"][NAME] = {"bound": bound, "least_s": least,
                          "kernel_s": k["seconds"], "calls": k["calls"]}
    return 100.0 * least / k["seconds"]
