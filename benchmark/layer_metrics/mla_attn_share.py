"""Kernels. Device time of the operations named `paged_attention_mla` (the
latent-cache attention kernel's `name=`, read from the operation's scope
path) over the device's busy time in the traced steps."""
import program_trace


def read(rec):
    return program_trace.share(rec, program_trace.has("paged_attention_mla"))
