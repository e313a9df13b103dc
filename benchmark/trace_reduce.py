"""From a profiler trace (`*.xplane.pb`, read with `jax.profiler.ProfileData`
and nothing else) to numbers: the seconds in which an operation ran on the
device, the table of device operations, and the idle gaps by what the host
was doing. Checked against a small recorded trace in `tests/`.

Planes named `/device:TPU:<n>` are devices. On each, the line `XLA Ops`
holds one event per executed HLO operation (nested ones, such as the body of
a `while`, overlap their parent: busy time is the UNION of intervals, and the
operation table counts leaf time only). Host spans are
`jax.profiler.TraceAnnotation`s written by the benchmark's own files; they
land on a `/host:CPU` plane on the same clock.
"""
from __future__ import annotations

import contextlib
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"


class Spans:
    """The benchmark's own host spans (`submit`, `fe.step`, `poll`,
    `make_batch`, `train_step`): on the profiler's clock while a trace is
    being taken (`on`), a no-op otherwise."""

    on = False

    def __call__(self, name):
        if self.on:
            import jax

            return jax.profiler.TraceAnnotation(name)
        return contextlib.nullcontext()


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals):
    """Total length and merged list of [start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _leaf_time(events):
    """name -> seconds, each event's duration less what its nested events
    cover (events are (start, end, name), times in ns)."""
    out = {}
    stack = []                       # [start, end, name, child_ns]

    def close(top):
        s, e, name, child = top
        out[name] = out.get(name, 0.0) + max(0.0, (e - s) - child) * 1e-9

    for s, e, name in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and s >= stack[-1][1]:
            close(stack.pop())
        if stack:
            stack[-1][3] += min(e, stack[-1][1]) - s
        stack.append([s, e, name, 0.0])
    while stack:
        close(stack.pop())
    return out


def reduce(path: str, span_names=()) -> dict:
    """The reduction. Returns
    {"devices": n, "window_s", "busy_s" (mean over devices),
     "ops": {name: seconds, summed over devices / devices},
     "op_events": [(start_s, end_s, name)] of device 0's leaf-level ops,
     "spans": {name: [(start_s, end_s)]}, "gaps": [(start_s, end_s)] of
     device 0}; times are seconds from the window's start. The traced
     window runs from the first to the last thing the trace holds, host
     span or device operation: the device's idle time before its first
     operation and after its last is part of it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    per_device = []
    host = {}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ev = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ev.append((e.start_ns, e.start_ns + e.duration_ns,
                               e.name))
            if ev:
                per_device.append((plane.name, ev))
        elif span_names and plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in span_names:
                        host.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    if not per_device:
        raise ValueError(f"{path}: no device plane with an "
                         f"'{OPS_LINE}' line; planes: "
                         f"{[p.name for p in data.planes]}")
    per_device.sort()
    hosted = [iv for v in host.values() for iv in v]
    t0 = min([s for _, ev in per_device for s, _, _ in ev]
             + [s for s, _ in hosted])
    t1 = max([e for _, ev in per_device for _, e, _ in ev]
             + [e for _, e in hosted])
    busy, ops = 0.0, {}
    for _, ev in per_device:
        b, _ = _union([(s, e) for s, e, _ in ev])
        busy += b * 1e-9
        for name, sec in _leaf_time(ev).items():
            ops[name] = ops.get(name, 0.0) + sec
    n = len(per_device)
    ev0 = per_device[0][1]
    _, merged = _union([(s, e) for s, e, _ in ev0])
    edges = [[t0, t0]] + merged + [[t1, t1]]
    gaps = [((a[1] - t0) * 1e-9, (b[0] - t0) * 1e-9)
            for a, b in zip(edges, edges[1:]) if b[0] > a[1]]
    return {
        "devices": n,
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": busy / n,
        "ops": {k: v / n for k, v in ops.items()},
        "op_events": [((s - t0) * 1e-9, (e - t0) * 1e-9, name)
                      for s, e, name in ev0],
        "spans": {k: [((s - t0) * 1e-9, (e - t0) * 1e-9) for s, e in v]
                  for k, v in host.items()},
        "gaps": gaps,
    }


def label(op_name: str) -> str:
    """A device operation's name as the trace gives it is its whole HLO line;
    the table keeps what is left of " = " and marks a Pallas call."""
    short = op_name.split(" = ")[0].lstrip("%")
    return short + " (tpu_custom_call)" if is_pallas(op_name) else short


def is_pallas(op_name: str) -> bool:
    """Whether a device operation is a Pallas (Mosaic) custom call."""
    return "tpu_custom_call" in op_name


def top_ops(reduced: dict, n: int = 10):
    return [[label(k), v] for k, v in sorted(reduced["ops"].items(),
                                             key=lambda kv: -kv[1])[:n]]


def idle_by_span(reduced: dict, n: int = 10):
    """Device-idle seconds charged to the host span that covers most of
    each gap ("unattributed" where none does), largest first."""
    spans = [(s, e, name) for name, iv in reduced["spans"].items()
             for s, e in iv]
    out = {}
    for gs, ge in reduced["gaps"]:
        best, cover = "unattributed", 0.0
        for s, e, name in spans:
            c = min(e, ge) - max(s, gs)
            if c > cover:
                best, cover = name, c
        out[best] = out.get(best, 0.0) + (ge - gs)
    return [[k, v] for k, v in sorted(out.items(),
                                      key=lambda kv: -kv[1])[:n]]
