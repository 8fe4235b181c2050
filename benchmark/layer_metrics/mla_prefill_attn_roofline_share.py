"""Kernels: the latent (MLA) ragged prefill attention kernel against its
roofline over the traced span: least time by the table of peaks for the
work the kernel had, over the kernel's time in the trace
(``mla_ragged_prefill_attention`` events, named after the kernel's function
in ``ops/attention_pallas.py``).

The work is the form the program takes (``harness/mla_cost.py``: absorbed
over the cached rows in place and over the wave's own; no cached row is
expanded), from the configuration file's published keys and the harness's
own records: a request whose first token came inside the span was
prefilled in it, in every layer, its prompt split into a cached prefix and
new tokens. The prefix is taken as the whole pages the prompt shares with
the same sender's previous prompt, the most the cache can have served
(``prefill_attn_roofline_share`` takes it so): where it served less the
kernel did more work than is counted and the share reads low, never high.
What the program itself counted of cached rows attended in the span
(counter ``latent_prefix_tokens_reused``) stands in the note beside the
records' sum, so that a cache that served less shows. A program without
the kernel reads nothing."""

from benchmark.harness import mla_cost, peaks, spec

NAME = "mla_prefill_attn_roofline_share"
KERNEL = "mla_ragged_prefill_attention"


def read(ctx):
    shared_pages = spec.load_reader(
        "prefill_attn_roofline_share").shared_pages
    tr = ctx["trace"]
    k = tr and tr["kernels"].get(KERNEL)
    if not k or k["seconds"] <= 0 or "kv_lora_rank" not in ctx["config"]:
        return None
    t0, t1 = ctx["trace_span"]
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
    cfg = ctx["config"]
    flops, moved = mla_cost.absorbed_prefill(cfg, rows)
    layers = cfg["num_hidden_layers"]
    least, bound = peaks.least_seconds(flops * layers, moved * layers,
                                       ctx["device_kind"])
    ctx["notes"][NAME] = {
        "bound": bound, "least_s": least, "kernel_s": k["seconds"],
        "calls": k["calls"], "requests": len(rows),
        "prefix_tokens_by_records": sum(p for p, _n in rows),
        "prefix_tokens_by_program": ctx["trace_counters"].get(
            "latent_prefix_tokens_reused")}
    return 100.0 * least / k["seconds"]
