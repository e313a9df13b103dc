"""Engine step. Per traced step, the wall time of the `fe.step` spans less
the device's busy time inside them: what the host adds to a step."""


def read(rec):
    tr = rec.get("trace")
    if not tr or not tr["spans"].get("fe.step"):
        return None
    spans = tr["spans"]["fe.step"]
    wall = sum(e - s for s, e in spans)
    busy = 0.0
    ops = sorted((s, e) for s, e, _ in tr["op_events"])
    for s, e in spans:
        cur = s
        for a, b in ops:
            if b <= cur or a >= e:
                continue
            a = max(a, cur)
            if b > a:
                busy += min(b, e) - a
                cur = min(b, e)
    return 1e3 * (wall - busy) / len(spans)
