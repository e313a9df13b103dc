"""Train step. Device time of the operations under the scopes `llama.head`
and `llama.loss` (the vocabulary gemm, its gradients and the cross entropy)
over the device's busy time in the traced steps."""
import program_trace


def read(rec):
    return program_trace.share(
        rec, program_trace.has("llama.head", "llama.loss"))
