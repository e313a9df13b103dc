"""Kernels. The least time the chip could take for the routed experts'
grouped matmuls in the traced steps (the three matrices of every expert
touched, once, and the routed rows in and out, at the published HBM rate:
at decode the experts are bound by their weights' bytes;
`costs_deepseek_v3.expert_bytes`) over the device time of the operations
under the scope `llama.moe_experts`. Experts touched and rows routed a step
are the engine's own device-side counters over the window (the traffic of a
cell that reads this is steady), times the traced steps."""
import costs_deepseek_v3
import program_trace


def read(rec):
    pt, load = program_trace.of(rec), rec.get("expert_load")
    if pt is None or not load or not load["steps"] or not rec.get("peaks"):
        return None
    spent = pt.op_seconds(rec["trace"]["ops"],
                          program_trace.has("llama.moe_experts"))
    if not spent:
        return None
    per_step = sum(
        costs_deepseek_v3.expert_bytes(rec["config"], touched, rows)
        for touched, rows in zip(load["touched"] / load["steps"],
                                 load["tokens"].sum(axis=1) / load["steps"]))
    least = per_step * rec["trace_steps"] / rec["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / spent
