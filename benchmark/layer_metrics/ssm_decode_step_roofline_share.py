"""Model: the decode steps of the traced span of a ``nemotron_h`` stack
against the least time the chip could take for what they had to move
(``harness/peaks.py``), over the decode programs' device time as
``decode_ms_per_step`` takes it.

What a step must move is counted by ``harness/ssm_cost.py`` from the
configuration file's published keys: every weight that is no routed
expert's once a step (the Mamba-2 and attention layers, the shared
experts, the routers, the held rows of the head), of the held experts
those the step's live rows chose (counters ``moe_expert_hits`` of
``moe_expert_step_slots``), the live rows' recurrent state once in a step
and once out a chunk, and their contexts' keys and values in the layers
that attend, from the harness's records as ``decode_step_roofline_share``
takes them. Steps are those some live row read; a step the device took for
no one counts as needing nothing. The bytes are the algorithm's, so it
reads under 100 whichever form the program keeps. A program without the
counters or a configuration without Mamba-2 layers reads nothing."""

from benchmark.harness import kernel_cost, peaks, ssm_cost

NAME = "ssm_decode_step_roofline_share"


def read(ctx):
    tr = ctx["trace"]
    c = ctx["trace_counters"]
    slots = c.get("moe_expert_step_slots", 0)
    cfg = ctx["config"]
    if tr is None or not slots or "hybrid_override_pattern" not in cfg:
        return None
    busy = sum(p["busy_s"] for n, p in tr["programs"].items()
               if "prefill" not in n and "decode" in n)
    if busy <= 0:
        return None
    m = ctx["model"]
    w = ssm_cost.weights(cfg)
    steps = slots / (w["routed_layers"] * w["held"])
    t0, t1 = ctx["trace_span"]
    layers = kernel_cost.attending_layers(cfg)
    row_steps = kv = 0.0
    for rec in ctx["engine_records"].values():
        a, b, n = rec["first_t"], rec["last_t"], rec["n_tokens"]
        if a is None or n < 2 or b <= a:
            continue
        lo, hi = max(a, t0), min(b, t1)
        if hi <= lo:
            continue
        took = (n - 1) * (hi - lo) / (b - a)
        context = len(rec["prompt"]) + n * ((lo + hi) / 2 - a) / (b - a)
        _f, by = kernel_cost.paged_decode_attention(
            [context], m.n_heads, m.n_kv_heads, m.head_dim)
        row_steps += took
        kv += took * by * layers
    hits = c.get("moe_expert_hits", 0)
    # a row-step makes top_k choices a routed layer; the held share of the
    # span's finished requests stands for the share of these
    made, held = c.get("moe_assignments", 0), c.get("moe_held_assignments", 0)
    held_choices = (row_steps * w["routed_layers"] * w["top_k"]
                    * (held / made if made else w["held"] / w["scored"]))
    flops, moved = ssm_cost.decode_steps(cfg, steps, hits, held_choices,
                                         row_steps, kv, ctx["decode_chunk"])
    least, bound = peaks.least_seconds(flops, moved, ctx["device_kind"])
    state = row_steps * ssm_cost.state_bytes(cfg) * (
        1 + 1 / ctx["decode_chunk"])
    ctx["notes"][NAME] = {
        "bound": bound, "least_s": least, "decode_s": busy, "steps": steps,
        "row_steps": row_steps, "bytes": moved,
        "fixed_bytes_a_step": w["fixed"], "expert_bytes": w["expert"],
        "state_bytes_share": state / moved,
        "held_experts_hit_a_step_a_layer": hits / (steps * w["routed_layers"]),
        "expert_share": hits / slots}
    return 100.0 * least / busy
