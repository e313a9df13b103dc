"""Kernels. `moe_experts_roofline` for a GLM-MoE-DSA chip that holds a share
of each layer's experts (`costs_glm_moe_dsa.expert_bytes`): the least time for
the three matrices of every HELD expert touched, once, and the rows routed to
held experts in and out, at the published HBM rate, over the device time of
the operations under the scope `llama.moe_experts`. Held experts touched and
rows routed to them a step are the engine's own device-side counters over the
window, times the traced steps."""
import check

import program_trace


def read(rec):
    pt, load = program_trace.of(rec), rec.get("expert_load")
    held = rec.get("held_experts")
    if pt is None or not load or not held or not load["steps"] \
            or not rec.get("peaks"):
        return None
    spent = pt.op_seconds(rec["trace"]["ops"],
                          program_trace.has("llama.moe_experts"))
    if not spent:
        return None
    costs = check.load("costs_glm_moe_dsa.py")
    first, count = held
    rows = load["tokens"][:, first:first + count].sum(axis=1) / load["steps"]
    per_step = sum(costs.expert_bytes(rec["config"], touched, r)
                   for touched, r in zip(load["touched"] / load["steps"], rows))
    least = per_step * rec["trace_steps"] / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / spent
