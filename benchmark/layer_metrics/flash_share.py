"""Kernels. Device time of the flash attention kernels by name (`flash_fwd`,
`flash_dq`, `flash_dkv` and their streamed variants) over the device's busy
time in the traced steps."""
import program_trace

KERNELS = tuple(k + s for k in ("flash_fwd", "flash_dq", "flash_dkv")
                for s in ("", "_streamed"))


def read(rec):
    return program_trace.share(rec, program_trace.has(*KERNELS))
