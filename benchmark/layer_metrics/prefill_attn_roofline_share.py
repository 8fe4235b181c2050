"""Kernels: the ragged paged prefill attention kernel against its
roofline over the traced span: least time by the table of peaks for the
work the kernel had, over the kernel's time in the trace
(``ragged_paged_prefill_attention`` events, named after the kernel's
function in ``ops/attention_pallas.py``).

The work comes from shapes (``harness/kernel_cost.py``) and from the
harness's own records: a request whose first token came inside the span
was prefilled in it, in every layer that attends
(``kernel_cost.attending_layers``), with its prompt split into a cached
prefix and new tokens. The engine does not say how much of a prompt it
found cached, so the prefix is taken as the whole pages the prompt shares
with the same sender's previous prompt: the most the cache can have
served. Where it served less the kernel did more work than is counted
here, and the share reads low, never high."""

from benchmark.harness import kernel_cost, peaks

KERNEL = "ragged_paged_prefill_attention"


def shared_pages(prompt, previous, page):
    n = 0
    for a, b in zip(prompt, previous):
        if a != b:
            break
        n += 1
    return min(n, len(prompt) - 1) // page * page


def read(ctx):
    tr = ctx["trace"]
    k = tr and tr["kernels"].get(KERNEL)
    if not k or k["seconds"] <= 0:
        return None
    t0, t1 = ctx["trace_span"]
    m = ctx["model"]
    last_prompt, rows = {}, []
    for row in sorted((r for r in ctx["rows"] if r["id"]),
                      key=lambda r: r["due"]):
        rec = ctx["engine_records"].get(row["id"])
        if rec is None:
            continue
        prompt = rec["prompt"]
        if rec["first_t"] is not None and t0 <= rec["first_t"] < t1:
            prefix = shared_pages(prompt, last_prompt.get(row["sender"], ()),
                                  ctx["page_size"])
            rows.append((prefix, len(prompt) - prefix))
        last_prompt[row["sender"]] = prompt
    if not rows:
        return None
    flops, moved = kernel_cost.ragged_prefill_attention(
        rows, m.n_heads, m.n_kv_heads, m.head_dim)
    layers = kernel_cost.attending_layers(ctx["config"])
    least, bound = peaks.least_seconds(flops * layers, moved * layers,
                                       ctx["device_kind"])
    ctx["notes"]["prefill_attn_roofline_share"] = {
        "bound": bound, "least_s": least, "kernel_s": k["seconds"],
        "calls": k["calls"], "requests": len(rows)}
    return 100.0 * least / k["seconds"]
