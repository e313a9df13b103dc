"""Train step. Device time of the operations under the scope `adamw` (the
optimizer's update loop) over the device's busy time in the traced steps. A
fusion counts where its root operation's scope puts it."""
import program_trace


def read(rec):
    return program_trace.share(rec, program_trace.has("adamw"))
