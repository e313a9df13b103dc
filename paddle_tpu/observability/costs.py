"""XLA cost-based accounting: per-executable `CostCard`s.

Every MFU / utilization claim in this repo used to rest on hand-coded
FLOP formulas (`model.flops_per_token`). The compiler already knows what
it compiled: `jit(f).lower(*avals).compile().cost_analysis()` reports
FLOPs and bytes accessed for the exact HLO that runs, and
`memory_analysis()` reports the executable's memory footprint. A
`CostCard` captures both; the `CostBook` caches cards alongside call
counts and wall time so:

- `bench.py` derives train MFU from compiler-reported FLOPs (the legacy
  formula stays as a cross-check, divergence > 10 % is reported);
- `profiler.summary()` prints a per-executable table
  (calls x wall-ms x achieved GFLOP/s).

`cost_analysis()` is never called unless the caller asks (bench) or
observability is enabled (serving dispatch wiring) — the
``observability.cost_analyses`` counter exists so tests can assert
exactly that.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional

__all__ = ["CostCard", "CostBook", "cost_book", "card_from_lowered",
           "card_for_jit", "ensure_engine_card", "record_call", "reset"]


class CostCard:
    """Compiler-reported cost of ONE executable (one jit signature)."""

    __slots__ = ("flops", "bytes_accessed", "peak_bytes", "argument_bytes",
                 "output_bytes", "temp_bytes")

    def __init__(self, flops: Optional[float] = None,
                 bytes_accessed: Optional[float] = None,
                 peak_bytes: Optional[int] = None,
                 argument_bytes: Optional[int] = None,
                 output_bytes: Optional[int] = None,
                 temp_bytes: Optional[int] = None):
        self.flops = flops
        self.bytes_accessed = bytes_accessed
        self.peak_bytes = peak_bytes
        self.argument_bytes = argument_bytes
        self.output_bytes = output_bytes
        self.temp_bytes = temp_bytes

    @classmethod
    def from_compiled(cls, compiled) -> "CostCard":
        """Build from a `jax` compiled executable (`lower().compile()`).
        `cost_analysis()` is a dict carrying "flops" and "bytes
        accessed"; keys a backend does not report stay None."""
        from ..framework import monitor

        monitor.inc("observability.cost_analyses")
        ca = compiled.cost_analysis() or {}
        flops = ca.get("flops")
        card = cls(flops=float(flops) if flops else None,
                   bytes_accessed=(float(ca["bytes accessed"])
                                   if ca.get("bytes accessed") else None))
        try:
            ma = compiled.memory_analysis()
            card.argument_bytes = int(getattr(ma, "argument_size_in_bytes",
                                              0) or 0)
            card.output_bytes = int(getattr(ma, "output_size_in_bytes",
                                            0) or 0)
            card.temp_bytes = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
            card.peak_bytes = (card.argument_bytes + card.output_bytes
                               + card.temp_bytes)
        except Exception:
            pass
        return card

    def as_dict(self) -> dict:
        return {"flops": self.flops, "bytes_accessed": self.bytes_accessed,
                "peak_bytes": self.peak_bytes,
                "argument_bytes": self.argument_bytes,
                "output_bytes": self.output_bytes,
                "temp_bytes": self.temp_bytes}

    def __repr__(self):
        f = "?" if self.flops is None else f"{self.flops / 1e9:.3f}G"
        return f"CostCard(flops={f}, bytes={self.bytes_accessed})"


def card_from_lowered(jit_fn, *args) -> CostCard:
    """Lower+compile `jit_fn` at `args` (arrays / pytrees of arrays /
    ShapeDtypeStructs — only shapes+dtypes matter, nothing executes) and
    read its cost/memory analysis."""
    import jax
    import numpy as np

    def struct(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        a = np.asarray(x) if not hasattr(x, "shape") else x
        return jax.ShapeDtypeStruct(a.shape, a.dtype)

    structs = jax.tree_util.tree_map(struct, args)
    return CostCard.from_compiled(jit_fn.lower(*structs).compile())


def card_for_jit(fn, *args) -> CostCard:
    """Convenience: `card_from_lowered(jax.jit(fn), *args)` for plain
    callables."""
    import jax

    return card_from_lowered(jax.jit(fn), *args)


class CostBook:
    """Registry: executable name -> (CostCard, call count, wall time).

    The card is the compiler's per-call cost; calls/wall come from the
    dispatch sites (`record_call`). `achieved GFLOP/s` =
    card.flops * calls / wall — utilization derived, not asserted."""

    def __init__(self):
        self._lock = threading.Lock()
        self._cards: Dict[str, Optional[CostCard]] = {}
        self._calls: Dict[str, int] = {}
        self._wall: Dict[str, float] = {}

    def register(self, name: str, card: Optional[CostCard]):
        with self._lock:
            self._cards[name] = card

    def has_card(self, name: str) -> bool:
        with self._lock:
            return self._cards.get(name) is not None

    def card(self, name: str) -> Optional[CostCard]:
        with self._lock:
            return self._cards.get(name)

    def record_call(self, name: str, wall_s: float):
        with self._lock:
            self._calls[name] = self._calls.get(name, 0) + 1
            self._wall[name] = self._wall.get(name, 0.0) + wall_s

    def rows(self) -> List[dict]:
        with self._lock:
            names = sorted(set(self._cards) | set(self._calls))
            out = []
            for n in names:
                card = self._cards.get(n)
                calls = self._calls.get(n, 0)
                wall = self._wall.get(n, 0.0)
                row = {"name": n, "calls": calls,
                       "wall_ms": round(wall * 1e3, 3),
                       "flops_per_call": card.flops if card else None,
                       "peak_bytes": card.peak_bytes if card else None,
                       "temp_bytes": card.temp_bytes if card else None,
                       "achieved_gflops": None}
                if card and card.flops and wall > 0 and calls:
                    # 3 significant digits: toy CPU shapes live far below
                    # 0.01 GFLOP/s and must not round to a broken-looking 0
                    row["achieved_gflops"] = float(
                        f"{card.flops * calls / wall / 1e9:.3g}")
                out.append(row)
            return out

    def reset(self):
        with self._lock:
            self._cards.clear()
            self._calls.clear()
            self._wall.clear()


_book = CostBook()


def cost_book() -> CostBook:
    return _book


def record_call(name: str, wall_s: float):
    _book.record_call(name, wall_s)


# phases whose card computation failed (or whose engine has no hook):
# tombstoned so the serving loop never re-pays a lower().compile()
# attempt per dispatch
_no_card: set = set()


def ensure_engine_card(name: str, engine, phase: str, call_args) -> bool:
    """Compute (once) the CostCard for an engine dispatch phase. Engines
    opt in by exposing `cost_card_args(phase) -> (jit_fn, leading_args)`
    (params/caches — the arguments the scheduler never sees); `call_args`
    are the scheduler-side arrays. Lowering re-traces the engine fn (the
    trace-time retrace counters tick once); callers snapshot those
    counters around this call. Best-effort: a missing hook or a failed
    lowering registers a tombstone and returns False — it must never
    retry on the dispatch hot path."""
    if _book.has_card(name):
        return True
    if name in _no_card:
        return False
    hook = getattr(engine, "cost_card_args", None)
    if hook is None:
        _no_card.add(name)
        return False
    try:
        jit_fn, leading = hook(phase)
        card = card_from_lowered(jit_fn, *leading, *call_args)
    except Exception:
        _no_card.add(name)
        return False
    _book.register(name, card)
    return True


def summary_lines() -> List[str]:
    """The profiler's "Executables:" section body."""
    rows = [r for r in _book.rows() if r["calls"] or r["flops_per_call"]]
    if not rows:
        return []
    lines = ["", f"{'Executable':<28}{'Calls':>7}{'Wall(ms)':>11}"
                 f"{'GFLOP/call':>12}{'GFLOP/s':>10}"]
    for r in rows:
        fpc = ("-" if r["flops_per_call"] is None
               else f"{r['flops_per_call'] / 1e9:.3f}")
        ach = "-" if r["achieved_gflops"] is None else str(r["achieved_gflops"])
        lines.append(f"{r['name'][:27]:<28}{r['calls']:>7}"
                     f"{r['wall_ms']:>11.2f}{fpc:>12}{ach:>10}")
    return lines


def reset():
    _book.reset()
    _no_card.clear()
