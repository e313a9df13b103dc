"""The program's one compile record: which function JAX traced, lowered,
compiled or read from the persistent cache, for how long, and — on a
retrace — WHAT changed versus the nearest cached signature.

**Fed by JAX itself, always on.** :func:`install` (called once at package
import, idempotent) registers listeners on `jax.monitoring` for the three
phases' start marks and time spans (`/jax/core/compile/jaxpr_trace_duration`,
`jaxpr_to_mlir_module_duration`, `backend_compile_duration`, each with
`fun_name`) and for the compilation cache's events. Each **top-level**
compile becomes ONE :class:`CompileRecord`; an event whose span lies inside
another's on the same thread belongs to the outer one (the inner `jit`s of
`jnp` functions fire while `_ragged_fn` is traced). The listeners run only
when JAX compiles, so a step pays nothing for them. Monitor counters beside
the record: ``compile.programs``, ``compile.trace_s``, ``compile.lower_s``,
``compile.backend_s``, ``compile.cache_hits``, ``compile.cache_misses``.

**What only the callers know** is attached to the record JAX's events made
(while `observability.enable()` is on; building a signature costs):

- `core.dispatch` (eager / lazy-region executables): a cache miss calls
  :func:`on_compile` with its structure key ``(name, attrs, avals, ...)``
  and gets the diff against the nearest cached key; after the first call
  :func:`attach` puts kind, op, key and cause on the record.
- the serving scheduler: every engine dispatch records its argument
  signature via :func:`note_signature`; when the engine's trace-time
  ``serving.*_retraces`` counter moved during the dispatch it calls
  :func:`note_retrace`, and the diff against the previous signature becomes
  the retrace CAUSE ("arg1 shape (1,16)->(1,32)") on that dispatch's record.

**Set-up that is not JAX's** (the package's import, an engine's build) is
stamped where it ends (:func:`stamp`): the host stamps a reader lays the
compile records against (:func:`stamps`), and their difference as the
monitor value ``<name>_s``.

:func:`compiles` / :func:`retrace_causes` are bounded deques;
`profiler.summary()` renders them as the "Compiles:" section.
"""
from __future__ import annotations

import re
import threading
import time
from collections import deque
from typing import Dict, List, Optional, Set, Tuple

__all__ = ["CompileRecord", "stamp", "install", "on_compile", "attach",
           "mark", "note_signature", "note_retrace", "diff_signatures",
           "compiles", "retrace_causes", "stamps", "reset"]

_MAX_RECORDS = 1024     # bounded: a long-running server must not grow
_MAX_KEYS_PER_NAME = 8  # cached signatures kept per executable name
_MAX_OPEN = 16          # traced / lowered programs a thread has not compiled

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_PHASES = (_TRACE, _LOWER, _BACKEND)
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# recorded where the compiled program is written to the cache: a program
# under the cache's size or compile-time threshold, or a process with no
# cache directory, reads "off"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileRecord:
    """One top-level compile: the seconds JAX spent tracing, lowering and
    compiling (or reading the persistent cache for) one program."""

    __slots__ = ("name", "trace_s", "lower_s", "backend_s", "cache",
                 "retrieval_s", "start", "end", "is_retrace",
                 "kind", "op", "key", "cause", "seq", "_tid")

    def __init__(self, name: str, trace_s: float, lower_s: float,
                 backend_s: float, cache: str, retrieval_s: float,
                 start: float, end: float, is_retrace: bool):
        self.name = name                # JAX's `fun_name`, unwrapped
        self.trace_s = trace_s
        self.lower_s = lower_s
        self.backend_s = backend_s      # compile, or the cache read
        self.cache = cache              # "hit" | "miss" | "off"
        self.retrieval_s = retrieval_s
        self.start = start              # host stamps (`time.time()`)
        self.end = end
        self.is_retrace = is_retrace    # `name` compiled before
        # what only the caller knows, attached after the fact
        self.kind: Optional[str] = None   # "fwd" | "fwd_vjp" | ... | "serving"
        self.op: Optional[str] = None     # dispatch op / "serve.<phase>"
        self.key = None
        self.cause: Optional[str] = None
        self.seq = 0
        self._tid = 0

    @property
    def wall_s(self) -> float:
        return self.trace_s + self.lower_s + self.backend_s

    def __repr__(self):
        tag = "retrace" if self.is_retrace else "compile"
        return (f"CompileRecord({tag} {self.name} trace {self.trace_s:.3f} "
                f"lower {self.lower_s:.3f} backend {self.backend_s:.3f} s "
                f"cache={self.cache}"
                + (f" cause={self.cause}" if self.cause else "") + ")")


_lock = threading.Lock()
_records: deque = deque(maxlen=_MAX_RECORDS)
_causes: deque = deque(maxlen=_MAX_RECORDS)
_seq = 0
_compiled: Set[str] = set()         # names compiled so far (is_retrace)
_stamps: Dict[str, Tuple[float, float]] = {}
# per (kind, name): recent structure keys, newest last
_seen: Dict[Tuple[str, str], deque] = {}
# per name: last argument signature (serving dispatch attribution)
_last_sig: Dict[str, tuple] = {}
_installed = False


class _Open(threading.local):
    """What this thread has traced or lowered and not yet compiled, and
    what the cache said inside the backend span that is still open."""

    def __init__(self):
        self.phases: List[str] = []      # begun and not ended, outermost first
        # top-level ones only, oldest first
        self.traces: deque = deque(maxlen=_MAX_OPEN)    # (start, end, name)
        self.lowered: deque = deque(maxlen=_MAX_OPEN)   # (start, fun_name,
        #                                    name, trace_s, lower_s)
        self.cache_miss = self.cache_hit = False
        self.retrieval_s = 0.0

    def take_cache(self) -> Tuple[str, float]:
        """What the cache said inside the backend span that just ended:
        (`hit` / `miss` / `off`, the retrieval's seconds)."""
        said = ("hit" if self.cache_hit else
                "miss" if self.cache_miss else "off"), self.retrieval_s
        self.cache_miss = self.cache_hit = False
        self.retrieval_s = 0.0
        return said


_open = _Open()


def reset():
    with _lock:
        _records.clear()
        _causes.clear()
        _compiled.clear()
        _seen.clear()
        _last_sig.clear()


def compiles() -> List[CompileRecord]:
    with _lock:
        return list(_records)


def retrace_causes() -> List[dict]:
    """Recorded retraces with their attributed cause, oldest first:
    ``{"name", "kind", "cause"}`` dicts."""
    with _lock:
        return list(_causes)


def stamps() -> Dict[str, Tuple[float, float]]:
    """Host stamps ``name -> (start, end)`` of the newest :func:`stamp` of
    each name, on the clock of the records' `start` / `end`."""
    with _lock:
        return dict(_stamps)


def mark() -> int:
    """The newest record's sequence number: hand it to :func:`attach` /
    :func:`note_retrace` after the call that may have compiled."""
    return _seq


# ---------------------------------------------------------------------------
# JAX's events
# ---------------------------------------------------------------------------


def _unwrapped(fun_name: str) -> str:
    """`jit(f)` / `pmap(f)` -> `f`: lowering and compiling are reported
    under the module's name, tracing under the function's."""
    m = re.fullmatch(r"\w+\((.*)\)", fun_name)
    return m.group(1) if m else fun_name


def _on_start(event: str, _start, **_):
    """JAX marks the START of each phase with a scalar under the phase's
    name: what this thread has open, so that an event ending inside
    another's span is known for an inner one."""
    if event in _PHASES:
        _open.phases.append(event)


def _on_span(event: str, start: float, end: float, fun_name: str = "", **_):
    if event not in _PHASES:
        return
    # close this phase (and whatever a failure left open above it)
    phases = _open.phases
    while phases and phases.pop() != event:
        pass
    if event == _BACKEND:
        cache, retrieval_s = _open.take_cache()
    if phases:
        # inside another phase of this thread (a `jnp` function traced
        # while `_ragged_fn` is; a constant computed eagerly during a
        # trace): the outer one's seconds hold these
        return
    if event == _TRACE:
        _open.traces.append((start, end, fun_name))
    elif event == _LOWER:
        name, trace_s, began = _unwrapped(fun_name), 0.0, start
        # the trace that ended as this lowering began is this program's
        # (`jit(f)(x)` and `.lower()` both trace, then lower); one of
        # another name is an `eval_shape` nobody compiled
        if _open.traces and name in (_open.traces[-1][2], "<unknown>"):
            began, t_end, name = _open.traces.pop()
            trace_s = t_end - began
        _open.lowered.append((began, fun_name, name, trace_s, end - start))
    else:
        _finish(fun_name, start, end, cache, retrieval_s)


def _finish(fun_name: str, start: float, end: float, cache: str,
            retrieval_s: float):
    global _seq
    from ..framework import monitor

    name, trace_s, lower_s, began = _unwrapped(fun_name), 0.0, 0.0, start
    for i in range(len(_open.lowered) - 1, -1, -1):
        if _open.lowered[i][1] == fun_name:
            began, _, name, trace_s, lower_s = _open.lowered[i]
            del _open.lowered[i]
            break
    with _lock:
        rec = CompileRecord(name, trace_s, lower_s, end - start, cache,
                            retrieval_s, began, end, name in _compiled)
        _compiled.add(name)
        _seq += 1
        rec.seq, rec._tid = _seq, threading.get_ident()
        _records.append(rec)
    monitor.inc("compile.programs")
    monitor.inc("compile.trace_s", trace_s)
    monitor.inc("compile.lower_s", lower_s)
    monitor.inc("compile.backend_s", rec.backend_s)
    if cache != "off":
        monitor.inc("compile.cache_hits" if cache == "hit"
                    else "compile.cache_misses")


def _on_event(event: str, **_):
    if event == _CACHE_HIT:
        _open.cache_hit = True
    elif event == _CACHE_MISS:
        _open.cache_miss = True


def _on_duration(event: str, seconds: float, **_):
    if event == _CACHE_RETRIEVAL:
        _open.retrieval_s = seconds


def install():
    """Register the listeners with `jax.monitoring`, once a process
    however often it is called (the package's import calls it)."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    from jax import monitoring

    monitoring.register_scalar_listener(_on_start)
    monitoring.register_event_time_span_listener(_on_span)
    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)


# ---------------------------------------------------------------------------
# set-up that is not JAX's
# ---------------------------------------------------------------------------


def stamp(name: str, began: float):
    """One phase of a set-up ends here, having begun at `began`
    (`time.time()`): the stamps under :func:`stamps` and the monitor value
    ``<name>_s`` on the host clock."""
    from ..framework import monitor

    end = time.time()
    with _lock:
        _stamps[name] = (began, end)
    monitor.set_value(name + "_s", end - began)


# ---------------------------------------------------------------------------
# signature diffing
# ---------------------------------------------------------------------------


def _fmt(v) -> str:
    s = str(v)
    return s if len(s) <= 48 else s[:45] + "..."


def _diff_avals(old, new, out: List[str]):
    if len(old) != len(new):
        out.append(f"arity {len(old)}->{len(new)}")
    for i in range(min(len(old), len(new), 16)):
        o, w = old[i], new[i]
        if o == w:
            continue
        if o is None or w is None:
            out.append(f"arg{i} {_fmt(o)}->{_fmt(w)}")
            continue
        oshape, odt = o[0], o[1]
        wshape, wdt = w[0], w[1]
        if oshape != wshape:
            out.append(f"arg{i} shape {oshape}->{wshape}")
        if str(odt) != str(wdt):
            out.append(f"arg{i} dtype {odt}->{wdt}")


def _diff_attrs(old, new, out: List[str]):
    od, nd = dict(old), dict(new)
    for k in sorted(set(od) | set(nd)):
        if k not in od:
            out.append(f"static_arg {k} added={_fmt(nd[k])}")
        elif k not in nd:
            out.append(f"static_arg {k} removed")
        elif od[k] != nd[k]:
            out.append(f"static_arg {k} {_fmt(od[k])}->{_fmt(nd[k])}")


def diff_signatures(old_key, new_key) -> List[str]:
    """Human-readable field-level diff of two dispatch structure keys
    ``(name, attrs, avals, *rest)`` or two plain aval signatures
    (tuples of (shape, dtype))."""
    out: List[str] = []
    if not (isinstance(old_key, tuple) and isinstance(new_key, tuple)):
        if old_key != new_key:
            out.append(f"signature {_fmt(old_key)}->{_fmt(new_key)}")
        return out
    # dispatch keys lead with the op name and pack attrs at [1], avals at
    # [2]; plain serving signatures are bare aval tuples
    if (len(old_key) >= 3 and isinstance(old_key[0], str)
            and len(new_key) >= 3 and isinstance(new_key[0], str)):
        _diff_attrs(old_key[1], new_key[1], out)
        _diff_avals(old_key[2], new_key[2], out)
        for i in range(3, min(len(old_key), len(new_key))):
            if old_key[i] != new_key[i]:
                out.append(f"key[{i}] {_fmt(old_key[i])}->{_fmt(new_key[i])}")
    else:
        _diff_avals(old_key, new_key, out)
    if not out and old_key != new_key:
        out.append("key changed (unattributed)")
    return out


def _nearest_cause(kind: str, name: str, key) -> Optional[str]:
    """Diff `key` against the nearest (fewest-differences) cached key for
    the same executable name."""
    prior = _seen.get((kind, name))
    if not prior:
        return None
    best: Optional[List[str]] = None
    for old in prior:
        d = diff_signatures(old, key)
        if best is None or len(d) < len(best):
            best = d
        if best is not None and len(best) == 1:
            break
    return "; ".join(best) if best else None


# ---------------------------------------------------------------------------
# what only the callers know
# ---------------------------------------------------------------------------


def attach(since: int, *, kind: str, op: str, key=None,
           cause: Optional[str] = None) -> Optional[CompileRecord]:
    """Put the caller's knowledge on the record JAX's events made on this
    thread after `since` (a :func:`mark`): the newest one, the call's own
    program (a conversion of its inputs compiles first). None where JAX
    compiled nothing, its own cache having served the call."""
    tid = threading.get_ident()
    with _lock:
        for r in reversed(_records):
            if r.seq <= since:
                break
            if r._tid == tid:
                r.kind, r.op, r.key, r.cause = kind, op, key, cause
                return r
    return None


def on_compile(kind: str, name: str, key) -> Optional[str]:
    """One executable-cache miss of the dispatch layer: remembers `key`
    and returns the cause, the diff against the nearest cached key of the
    same op (None on its first compile). The caller hands it to
    :func:`attach` after the first call."""
    with _lock:
        cause = _nearest_cause(kind, name, key)
        is_retrace = (kind, name) in _seen
        _seen.setdefault((kind, name),
                         deque(maxlen=_MAX_KEYS_PER_NAME)).append(key)
        if is_retrace:
            cause = cause or "first signature change"
            _causes.append({"name": name, "kind": kind, "cause": cause})
    return cause


def note_signature(name: str, sig: tuple):
    """Remember the latest argument signature for `name` (serving engine
    dispatch); the baseline a later retrace is diffed against."""
    with _lock:
        _last_sig[name] = sig


def note_retrace(name: str, sig: tuple, since: int) -> Optional[str]:
    """The dispatch under `name` retraced with signature `sig`: attribute
    it against the previous signature and put the cause on the record the
    dispatch made (after `since`, a :func:`mark`). Returns the cause, or
    None when this was the FIRST trace of `name` — a compile, not a
    retrace; callers must not count a cause for it."""
    with _lock:
        prev = _last_sig.get(name)
        if prev is None:
            cause = None
        else:
            d = diff_signatures(prev, sig)
            cause = "; ".join(d) if d else "identical signature (jit-internal)"
            _causes.append({"name": name, "kind": "serving", "cause": cause})
        _last_sig[name] = sig
    attach(since, kind="serving", op=name, key=sig, cause=cause)
    return cause


def summary_lines() -> List[str]:
    """The profiler's "Compiles:" section body."""
    with _lock:
        records = list(_records)
        causes = list(_causes)
    if not records:
        return []
    lines = ["",
             f"Compiles: {len(records)} programs "
             f"({sum(r.is_retrace for r in records)} retraces), trace "
             f"{sum(r.trace_s for r in records):.2f} s, lowering "
             f"{sum(r.lower_s for r in records):.2f} s, backend "
             f"{sum(r.backend_s for r in records):.2f} s; cache "
             f"{sum(r.cache == 'hit' for r in records)} hits, "
             f"{sum(r.cache == 'miss' for r in records)} misses"]
    for r in sorted(records, key=lambda r: -r.wall_s)[:8]:
        lines.append(f"  {r.name}: trace {r.trace_s:.3f} s, lowering "
                     f"{r.lower_s:.3f} s, backend {r.backend_s:.3f} s "
                     f"({r.cache})" + (" retrace" if r.is_retrace else ""))
    for c in causes[-8:]:
        lines.append(f"  retrace {c['name']}: {c['cause']}")
    return lines
