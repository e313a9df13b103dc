"""Kernels. The least time the chip could take for the bytes the one-token
state update needs in the traced steps (every live lane's state of every
layer in and out once: `costs_brumby.retention_update_bytes`, tallied by the
runner a traced step) at the published HBM rate, over the device time of the
kernel `power_retention_update`, told by its name. Bytes-bound: the update
does two FLOPs a byte it moves."""
import program_trace


def read(rec):
    pt = program_trace.of(rec)
    if pt is None or not rec.get("retention_update_bytes_traced") \
            or not rec.get("peaks"):
        return None
    kernel = pt.op_seconds(rec["trace"]["ops"],
                           program_trace.has("power_retention_update"))
    if not kernel:
        return None
    return (100.0 * rec["retention_update_bytes_traced"]
            / rec["peaks"]["hbm_bytes_per_s"] / kernel)
