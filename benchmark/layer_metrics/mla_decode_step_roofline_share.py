"""Model: the decode steps of the traced span of a ``deepseek_v2`` stack
against the least time the chip could take for what they had to move
(``harness/peaks.py``), over the decode programs' device time as
``decode_ms_per_step`` takes it.

What a step must move is counted by ``harness/mla_cost.py`` from the
configuration file's published keys: every weight that is no routed
expert's once a step (attention, the dense layer, the shared experts, the
routers, the held rows of the head), of the held experts those the step's
live rows chose (counters ``moe_expert_hits`` of ``moe_expert_step_slots``:
distinct held experts a step and a routed layer, and the held experts it
could have), and the live rows' cached latent rows with their queries and
outputs, from the harness's records as ``mla_decode_attn_roofline_share``
takes them. Steps are those some live row read; a step the device took for
no one counts as needing nothing. The bytes are the algorithm's, so it
reads under 100 whichever dispatch the program keeps. A program without
the counters or a configuration without latent pages reads nothing."""

from benchmark.harness import mla_cost, peaks, spec

NAME = "mla_decode_step_roofline_share"


def read(ctx):
    tr = ctx["trace"]
    c = ctx["trace_counters"]
    slots = c.get("moe_expert_step_slots", 0)
    if tr is None or not slots or "kv_lora_rank" not in ctx["config"]:
        return None
    busy = sum(p["busy_s"] for n, p in tr["programs"].items()
               if "prefill" not in n and "decode" in n)
    if busy <= 0:
        return None
    cfg = ctx["config"]
    w = mla_cost.weights(cfg)
    steps = slots / (w["routed_layers"] * w["held"])
    rows = spec.load_reader("mla_decode_attn_roofline_share").row_steps(ctx)
    row_steps = sum(s for s, _c in rows)
    latent = sum(s * mla_cost.absorbed_decode(cfg, [ctxt])[1]
                 for s, ctxt in rows) * cfg["num_hidden_layers"]
    hits = c.get("moe_expert_hits", 0)
    # a row-step makes top_k choices a routed layer; the held share of the
    # span's finished requests stands for the share of these
    made, held = c.get("moe_assignments", 0), c.get("moe_held_assignments", 0)
    held_choices = (row_steps * w["routed_layers"] * w["top_k"]
                    * (held / made if made else w["held"]
                       / cfg["n_routed_experts"]))
    flops, moved = mla_cost.decode_steps(cfg, steps, hits, held_choices,
                                         row_steps, latent)
    least, bound = peaks.least_seconds(flops, moved, ctx["device_kind"])
    ctx["notes"][NAME] = {
        "bound": bound, "least_s": least, "decode_s": busy, "steps": steps,
        "row_steps": row_steps, "bytes": moved,
        "fixed_bytes_a_step": w["fixed"], "expert_bytes": w["expert"],
        "held_experts_hit_a_step_a_layer": hits / (steps * w["routed_layers"]),
        "expert_share": hits / slots}
    return 100.0 * least / busy
