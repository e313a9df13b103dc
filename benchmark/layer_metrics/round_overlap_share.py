"""Scheduler. Of the window's rounds (monitor counter `serving.step.programs`:
one device program a plain round), the share launched while the round before
was still unfetched (`serving.step.overlapped`, PR 43), in %: the scheduler's
Python, the launch and the fetch of such a round run under the device's work.
A program that counts no rounds gives nothing to read."""


def read(rec):
    rounds = rec.get("rounds")
    if not rounds or not rounds["programs"]:
        return None
    return 100.0 * rounds["overlapped"] / rounds["programs"]
