"""The program's own spans and device scopes, read from a traced run's
`.xplane.pb`: what the per-layer readers `sched_*`, `idle_under_sched_*`,
`admit_step_extra_ms`, `lane_wait_p50_ms`, `*_share` and
`ragged_attn_roofline` share. `of(rec)` opens the newest trace under
`.bench_trace/` once per process and returns `None` where there is none to
read (an untraced run, the CPU rehearsal, a program without these spans).

Two things are read, both written by the program (docs/OBSERVABILITY.md,
"Program spans and device scopes"):

- **Spans** `frontend.*` / `sched.*`: `paddle_tpu.profiler.RecordEvent`
  enters a `jax.profiler.TraceAnnotation`, so under a profiler session they
  land on a `/host:CPU` line with their ids as stats. A span's children are
  the spans inside it by time on the same line; its self time is its wall
  less its children's.
- **Scopes**: `jax.named_scope` regions (`llama.attn`, `adamw`, ...) and the
  Pallas kernels' `name=` end up in each HLO operation's `op_name`, which
  the TPU profiler keeps as the `tf_op` stat of the operation's *event
  metadata*. `jax.profiler.ProfileData` does not surface event metadata, so
  `op_scopes` reads it from the file's protobuf wire format (XSpace ->
  XPlane -> event_metadata) and joins it to the device events by the
  metadata's name, which is the event's. An operation whose metadata has no
  `tf_op`, or one that names no region of the program, is *unscoped*: it is
  counted as such, never guessed.
"""
from __future__ import annotations

import glob
import os
import re

import trace_reduce

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPAN_PREFIXES = ("frontend.", "sched.")
STEP = "sched.step"
# the spans whose body is an engine call or a host fetch: there the host
# waits for the device; the rest of `sched.step` is the scheduler's Python.
# (A round is one launch and one fetch since PR 30; `sched.screen` is a span
# of the speculative round alone, which no cell runs.)
WAITING = ("sched.dispatch", "sched.sample")
# what counts as a region of the program in an operation's scope path
REGION_PREFIX = "llama."
REGIONS = ("sampler", "adamw")
_WRAPPED = re.compile(r"^(?:\w+\()+([^()]+)\)+$")     # transpose(jvp(x)) -> x


class Span:
    __slots__ = ("name", "start", "end", "ids", "children")

    def __init__(self, name, start, end, ids):
        self.name, self.start, self.end, self.ids = name, start, end, ids
        self.children = []

    @property
    def wall(self):
        return self.end - self.start

    @property
    def self_time(self):
        return self.wall - sum(c.wall for c in self.children)

    def walk(self):
        """This span and every span under it."""
        yield self
        for c in self.children:
            yield from c.walk()

    def holds(self, *names):
        return any(s.name in names for s in self.walk() if s is not self)


# ---- the file's wire format (protobuf, no dependency) ----------------------
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind} in an .xplane.pb")
        yield key >> 3, value


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def op_scopes(path: str) -> dict:
    """{event-metadata name: scope path} over the device planes of an
    `.xplane.pb`: XSpace.planes(1) -> XPlane{name(2), event_metadata(4):
    map<id, XEventMetadata{name(2), stats(5)}>, stat_metadata(5): map<id,
    XStatMetadata{name(2)}>}; XStat{metadata_id(1), str_value(5),
    ref_value(7)}. The `tf_op` stat is "<op_name>:<op type>"; what is
    left of the last colon is the scope path. A name whose metadata
    disagree (two modules, one instruction text) maps to ""."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    scopes = {}
    for no, plane in _fields(space):
        if no != 1:
            continue
        name, metadata, stat_names = "", [], {}
        for no, value in _fields(plane):
            if no == 2:
                name = _text(value)
            elif no == 4:
                metadata.append(value)
            elif no == 5:
                entry = dict(_fields(value))
                stat_names[entry.get(1, 0)] = _text(
                    dict(_fields(entry[2])).get(2, b""))
        if not name.startswith(trace_reduce.DEVICE_PREFIX):
            continue
        tf_op = {i for i, n in stat_names.items() if n == "tf_op"}
        for entry in metadata:
            op, scope = "", ""
            for no, value in _fields(dict(_fields(entry))[2]):
                if no == 2:
                    op = _text(value)
                elif no == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) in tf_op:
                        raw = (_text(stat[5]) if 5 in stat
                               else stat_names.get(stat.get(7), ""))
                        scope = raw.rpartition(":")[0] or raw
            if op in scopes and scopes[op] != scope:
                scope = ""
            scopes[op] = scope
    return scopes


def tokens(scope: str):
    """A scope path's components, transformation wrappers taken off:
    `jit(f)/transpose(jvp(llama.layer))/llama.attn/flash_dq/pallas_call`
    -> [f, llama.layer, llama.attn, flash_dq, pallas_call]. Where XLA
    merged two operations their paths are joined by ";": the first is
    the operation's own."""
    out = []
    for part in scope.partition(";")[0].split("/"):
        m = _WRAPPED.match(part)
        out.append(m.group(1) if m else part)
    return out


def region(scope: str):
    """The innermost region of the program a scope path names, or None."""
    for tok in reversed(tokens(scope)):
        if tok.startswith(REGION_PREFIX) or tok in REGIONS:
            return tok
    return None


def kernel(scope: str):
    """The Pallas kernel's `name=` if the path ends in its call."""
    toks = tokens(scope)
    if len(toks) >= 2 and toks[-1] == "pallas_call":
        return toks[-2]
    return None


# ---- one traced run ----------------------------------------------------------
class ProgramTrace:
    """Spans (times in seconds on the profiler's clock, from its first
    event) and the scope of every device operation of one `.xplane.pb`."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData

        self.path = path
        data = ProfileData.from_file(path)
        lines, device = [], []
        for plane in data.planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    ev = [Span(e.name, e.start_ns * 1e-9,
                               (e.start_ns + e.duration_ns) * 1e-9,
                               dict(e.stats))
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIXES)
                          or e.name == "fe.step"]
                    if ev:
                        lines.append(ev)
            elif plane.name.startswith(trace_reduce.DEVICE_PREFIX) \
                    and not device:
                for line in plane.lines:
                    if line.name == trace_reduce.OPS_LINE:
                        device = [(e.start_ns * 1e-9,
                                   (e.start_ns + e.duration_ns) * 1e-9)
                                  for e in line.events]
        self.roots = []                   # spans with no span around them
        for ev in lines:
            stack = []
            for s in sorted(ev, key=lambda s: (s.start, -s.end)):
                while stack and s.start >= stack[-1].end:
                    stack.pop()
                (stack[-1].children if stack else self.roots).append(s)
                stack.append(s)
        self.spans = [s for r in self.roots for s in r.walk()]
        self.steps = [s for s in self.spans if s.name == STEP]
        # [[start, end]] in which an operation ran, merged and sorted
        _, self.busy = trace_reduce._union(device)
        self.scopes = op_scopes(path)

    # -- spans --
    def named(self, name):
        return [s for s in self.spans if s.name == name]

    def waiting_s(self, step):
        return sum(s.wall for s in step.walk() if s.name in WAITING)

    def idle_s(self, start, end):
        """Seconds of [start, end) in which no device operation ran."""
        covered = sum(min(e, end) - max(s, start) for s, e in self.busy
                      if e > start and s < end)
        return (end - start) - covered

    def idle_outside_waiting_s(self, step):
        """Device-idle seconds inside a `sched.step` and outside its
        waiting spans: the idle that the scheduler's own Python causes."""
        idle = self.idle_s(step.start, step.end)
        for s in step.walk():
            if s.name in WAITING:
                idle -= self.idle_s(s.start, s.end)
        return idle

    def gaps_outside_spans(self, longer_than_s=1e-3, slack_s=1e-4):
        """Device-idle gaps longer than `longer_than_s` inside a `fe.step`
        of which more than `slack_s` lies outside every `sched.*` span
        (`fe.step` begins some 20 us before its `sched.step` and ends 5 us
        after): [(start, end, seconds outside)]. None is the healthy
        answer: every long gap is then laid to a phase of the scheduler."""
        _, program = trace_reduce._union(
            [(s.start, s.end) for s in self.spans
             if s.name.startswith("sched.")])
        out = []
        for fe in self.named("fe.step"):
            edges = [[fe.start, fe.start]] + [
                [max(s, fe.start), min(e, fe.end)] for s, e in self.busy
                if e > fe.start and s < fe.end] + [[fe.end, fe.end]]
            for a, b in zip(edges, edges[1:]):
                gs, ge = a[1], b[0]
                if ge - gs <= longer_than_s:
                    continue
                inside = sum(min(e, ge) - max(s, gs) for s, e in program
                             if e > gs and s < ge)
                if (ge - gs) - inside > slack_s:
                    out.append((gs, ge, (ge - gs) - inside))
        return out

    # -- device time by what the program calls it --
    def op_seconds(self, ops: dict, match):
        """Sum of `ops` (name -> leaf seconds, `trace_reduce.reduce`'s)
        over the operations whose scope path `match` accepts."""
        return sum(sec for name, sec in ops.items()
                   if match(self.scopes.get(name, "")))

    def by_region(self, ops: dict):
        """{region or kernel or "(unscoped)": seconds}, for the log."""
        out = {}
        for name, sec in ops.items():
            scope = self.scopes.get(name, "")
            key = kernel(scope) or region(scope) or "(unscoped)"
            out[key] = out.get(key, 0.0) + sec
        return out


_OPEN = {}


def newest_xplane():
    """This run's trace: `run.py` clears the cell's directory before a
    traced run, so the newest directory under `.bench_trace/` is its."""
    dirs = [d for d in glob.glob(os.path.join(ROOT, ".bench_trace", "*"))
            if os.path.isdir(d)]
    if not dirs:
        return None
    try:
        return trace_reduce.find_xplane(max(dirs, key=os.path.getmtime))
    except FileNotFoundError:
        return None


def of(rec):
    """The run's `ProgramTrace`, or None: no reduced trace in the record
    (untraced, or the CPU rehearsal) or no trace file."""
    if not rec.get("trace"):
        return None
    path = newest_xplane()
    if path is None:
        return None
    if path not in _OPEN:
        _OPEN[path] = ProgramTrace(path)
    return _OPEN[path]


def share(rec, match):
    """100 x device seconds of the operations `match` accepts (by scope
    path) over the device's busy seconds; None without a trace or where
    the program wrote no scope at all (the parent of the PR that named
    them)."""
    pt = of(rec)
    if pt is None or not any(region(s) for s in pt.scopes.values()):
        return None
    tr = rec["trace"]
    return 100.0 * pt.op_seconds(tr["ops"], match) / tr["busy_s"]


def has(*names):
    """A matcher: the scope path holds one of `names` as a component."""
    return lambda scope: any(t in names for t in tokens(scope))

