"""Device. One minus the union of the device-operation intervals over the
traced window."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
