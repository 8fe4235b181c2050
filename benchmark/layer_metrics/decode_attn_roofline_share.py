"""Kernels: the paged (chunked) decode attention kernel against its
roofline over the traced span: least time by the table of peaks for the
work the kernel had, over the kernel's time in the trace
(``paged_decode_gqa_attention_chunked`` events, named after the kernel's
function in ``ops/attention_pallas.py``).

The work comes from shapes (``harness/kernel_cost.py``) and from the
harness's own records: a request decodes one token a step between its
first and its last token, at a context of its prompt plus what it has
generated so far, in every layer that attends
(``kernel_cost.attending_layers``). The part of each request that falls
into the span is taken in proportion to time."""

from benchmark.harness import kernel_cost, peaks

KERNEL = "paged_decode_gqa_attention_chunked"


def read(ctx):
    tr = ctx["trace"]
    k = tr and tr["kernels"].get(KERNEL)
    if not k or k["seconds"] <= 0:
        return None
    t0, t1 = ctx["trace_span"]
    m = ctx["model"]
    layers = kernel_cost.attending_layers(ctx["config"])
    flops = moved = 0.0
    for rec in ctx["engine_records"].values():
        a, b, n = rec["first_t"], rec["last_t"], rec["n_tokens"]
        if a is None or n < 2 or b <= a:
            continue
        lo, hi = max(a, t0), min(b, t1)
        if hi <= lo:
            continue
        steps = (n - 1) * (hi - lo) / (b - a)
        context = len(rec["prompt"]) + n * ((lo + hi) / 2 - a) / (b - a)
        f, by = kernel_cost.paged_decode_attention(
            [context], m.n_heads, m.n_kv_heads, m.head_dim)
        flops += steps * f * layers
        moved += steps * by * layers
    if not moved:
        return None
    least, bound = peaks.least_seconds(flops, moved, ctx["device_kind"])
    ctx["notes"]["decode_attn_roofline_share"] = {
        "bound": bound, "least_s": least, "kernel_s": k["seconds"],
        "calls": k["calls"]}
    return 100.0 * least / k["seconds"]
