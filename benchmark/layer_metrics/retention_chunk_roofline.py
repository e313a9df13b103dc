"""Kernels. The least time the chip could take for what the chunked form of
power retention had to do in the traced steps, over the device time of the
kernel `power_retention_chunk`, told by its name. What it had to do is read
off the scheduler's own spans: every traced `sched.dispatch` carries the
step's `prefill_tokens`. How they split over lanes it does not say, so a
step's chunk is counted at the LEAST it can be: one lane's state in and out
once (`costs_brumby.retention_chunk_bytes` of one chunk) and the rows' FLOPs
without their causal pairs (`retention_chunk_flops` a row at a time); the
larger of bytes at the published HBM rate and FLOPs at the published bf16
peak, times the run's layers. The share reads low by that, never high."""
import check

import program_trace


def read(rec):
    pt = program_trace.of(rec)
    if pt is None or not rec.get("peaks"):
        return None
    kernel = pt.op_seconds(rec["trace"]["ops"],
                           program_trace.has("power_retention_chunk"))
    chunks = [int(s.ids.get("prefill_tokens") or 0)
              for s in pt.named("sched.dispatch")]
    chunks = [n for n in chunks if n > 0]
    if not kernel or not chunks:
        return None
    costs = check.load("costs_brumby.py")
    cfg, peaks = rec["config"], rec["peaks"]
    least = sum(max(costs.retention_chunk_bytes(cfg, [n])
                    / peaks["hbm_bytes_per_s"],
                    costs.retention_chunk_flops(cfg, [1] * n)
                    / peaks["bf16_flops_per_s"]) for n in chunks)
    return 100.0 * cfg["num_hidden_layers"] * least / kernel
