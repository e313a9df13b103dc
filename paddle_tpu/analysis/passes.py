"""ptlint tier A: AST source passes over the paddle_tpu package.

Five passes, each machine-checking an invariant the review history kept
re-finding by hand (ISSUE 13):

- ``use-after-donate``   — a binding passed at a donated position of a
  ``jax.jit(..., donate_argnums=...)`` callable is DELETED by the call;
  reading it afterwards (PR 3's snapshot bug) is flagged unless the
  statement rebinds it from the call's results.
- ``trace-hazard``       — inside jit-traced function bodies: host
  conversions (``float()/int()/bool()/.item()``), ``np.asarray`` host
  materialization, data-dependent Python ``if`` on traced values, and
  trace-time nondeterminism (clocks, host RNG) that bakes one draw into
  the compiled program.
- ``hot-path``           — inside declared hot paths (registry +
  ``# ptlint: hot-path``): per-call device transfers (``jnp.asarray`` /
  ``device_put``), per-call imports, blocking I/O, and direct
  ``monitor`` writes not behind the observability enable bool
  (`self.metrics.on_*` is the sanctioned always-on channel).
- ``zero-cost-off``      — every observability payload producer call
  outside ``paddle_tpu/observability/`` must be syntactically gated by
  the one enable bool (the PR 7 contract, asserted point-wise until
  now). Functions documented as gated-callees (registry or
  ``# ptlint: gated-callee``) are exempt inside — and calls TO them
  must themselves be gated.
- ``lock-hygiene``       — in declared threaded modules: writes to
  state that is elsewhere mutated under a lock, outside any
  ``with <lock>`` block; and sleeps/joins/subprocess calls held UNDER a
  lock.

Everything is syntactic and conservative-by-declaration: the registry
(`registry.py`) + in-source pragmas define the contract surface, the
baseline (`findings.py`) ratchets pre-existing violations out. STDLIB
ONLY — no jax, no paddle_tpu import (tools/ptlint.py loads this package
standalone).
"""
from __future__ import annotations

import ast
import os
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from . import registry
from .findings import Finding

__all__ = ["PASS_IDS", "scan_file", "scan_paths", "collect_files"]

PASS_IDS = ("use-after-donate", "trace-hazard", "hot-path",
            "zero-cost-off", "lock-hygiene")

_PRAGMA_RE = re.compile(r"#\s*ptlint:\s*([a-z-]+(?:=[\w,-]+)?)")


# ---------------------------------------------------------------------------
# shared AST infrastructure
# ---------------------------------------------------------------------------


def _dotted(node) -> Optional[str]:
    """'self.engine.manager' for nested Attribute/Name chains; None for
    anything else (calls, subscripts)."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _call_name(call: ast.Call) -> Optional[str]:
    return _dotted(call.func)


class Module:
    """One parsed file + the derived maps every pass shares."""

    def __init__(self, path: str, relpath: str, source: str):
        self.relpath = relpath.replace(os.sep, "/")
        self.tree = ast.parse(source, filename=path)
        self.lines = source.splitlines()
        self.parents: Dict[ast.AST, ast.AST] = {}
        self.qualname: Dict[ast.AST, str] = {}
        self.pragmas: Dict[int, List[str]] = {}
        for i, ln in enumerate(self.lines, 1):
            if "ptlint" in ln:
                self.pragmas[i] = _PRAGMA_RE.findall(ln)
        self._index()

    def _index(self):
        stack: List[str] = []

        def walk(node, parent):
            for child in ast.iter_child_nodes(node):
                self.parents[child] = node
                if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                      ast.ClassDef)):
                    stack.append(child.name)
                    self.qualname[child] = ".".join(stack)
                    walk(child, child)
                    stack.pop()
                else:
                    walk(child, node)

        walk(self.tree, self.tree)

    def functions(self) -> Iterable[Tuple[str, ast.AST]]:
        for node, qn in self.qualname.items():
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                yield qn, node

    def enclosing_function(self, node) -> Optional[ast.AST]:
        cur = self.parents.get(node)
        while cur is not None:
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return cur
            cur = self.parents.get(cur)
        return None

    def scope_of(self, node) -> str:
        fn = self.enclosing_function(node)
        return self.qualname.get(fn, "") if fn is not None else ""

    def has_pragma(self, node, directive: str) -> bool:
        line = getattr(node, "lineno", None)
        return bool(line) and any(p.startswith(directive)
                                  for p in self.pragmas.get(line, []))


def _is_enable_call(node, gate_names: Set[str]) -> bool:
    """`_obs.enabled()` / `observability.enabled()` / `enabled()`, or a
    variable bound from one (`obs_on`)."""
    if isinstance(node, ast.Call):
        d = _call_name(node)
        if d and d.split(".")[-1] in registry.ENABLE_CHECK_NAMES:
            return True
    if isinstance(node, ast.Name) and node.id in gate_names:
        return True
    return False


def _gate_polarity(test, gate_names: Set[str]) -> Optional[bool]:
    """True: test passing implies enabled. False: implies disabled.
    None: not a gate test."""
    if _is_enable_call(test, gate_names):
        return True
    if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
        inner = _gate_polarity(test.operand, gate_names)
        return None if inner is None else not inner
    if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.And):
        # `enabled() and x`: the body only runs enabled
        for v in test.values:
            if _gate_polarity(v, gate_names) is True:
                return True
    return None


def _gate_names(fn, module: Module) -> Set[str]:
    """Local variables assigned from an enable check
    (`obs_on = _obs.enabled()`)."""
    names: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            d = _call_name(node.value)
            if d and d.split(".")[-1] in registry.ENABLE_CHECK_NAMES:
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        names.add(t.id)
    return names


def _is_gated(node, module: Module, fn=None) -> bool:
    """Is `node` only reachable with the observability layer enabled?

    Recognized shapes: `if <gate>:` ancestors (node in body), `if not
    <gate>:` ancestors (node in orelse), `<x> if <gate> else <y>`
    ternaries, `<gate> and <x>` operands, and the early-exit idiom
    (`if not <gate>: return ...` earlier in the function body).

    The walk crosses nested-def boundaries: a closure defined inside
    `if <gate>:` (or in a function that early-exited on disabled) only
    comes into existence with the layer on, so its body is gated."""
    fn = fn or module.enclosing_function(node)
    gates: Set[str] = set()
    enc = fn
    while enc is not None:
        gates |= _gate_names(enc, module)
        enc = module.enclosing_function(enc)
    cur, child = module.parents.get(node), node
    while cur is not None:
        if isinstance(cur, ast.If):
            pol = _gate_polarity(cur.test, gates)
            if pol is True and _in_subtree(child, cur.body):
                return True
            if pol is False and _in_subtree(child, cur.orelse):
                return True
        elif isinstance(cur, ast.IfExp):
            pol = _gate_polarity(cur.test, gates)
            if pol is True and _in_subtree(child, [cur.body]):
                return True
            if pol is False and _in_subtree(child, [cur.orelse]):
                return True
        elif isinstance(cur, ast.BoolOp) and isinstance(cur.op, ast.And):
            for i, v in enumerate(cur.values):
                if _in_subtree(child, [v]):
                    if any(_gate_polarity(prev, gates) is True
                           for prev in cur.values[:i]):
                        return True
        child, cur = cur, module.parents.get(cur)
    # early-exit dominance: `if not <gate>: return` before this statement
    # — checked at EVERY enclosing function level (an outer early exit
    # dominates a nested def's body too)
    node_line = getattr(node, "lineno", 0)
    enc = fn
    while enc is not None:
        for stmt in enc.body:
            if stmt.lineno >= node_line:
                break
            if isinstance(stmt, ast.If) and not stmt.orelse and stmt.body \
                    and isinstance(stmt.body[-1],
                                   (ast.Return, ast.Raise, ast.Continue)) \
                    and _gate_polarity(stmt.test, gates) is False:
                return True
        enc = module.enclosing_function(enc)
    return False


def _in_subtree(node, stmts) -> bool:
    return any(node is s or any(node is d for d in ast.walk(s))
               for s in (stmts or []))


def _statement_of(node, module: Module):
    cur = node
    while cur is not None and not isinstance(cur, ast.stmt):
        cur = module.parents.get(cur)
    return cur


# ---------------------------------------------------------------------------
# jit-site parsing (shared by use-after-donate and trace-hazard)
# ---------------------------------------------------------------------------

_JIT_NAMES = {"jit"}          # jax.jit / jit / api.jit — match last segment


class JitSite:
    """One `jax.jit(fn, ...)` call: the wrapped fn expression, donated
    positions/names, static positions/names."""

    __slots__ = ("call", "inner", "donate_idx", "donate_names",
                 "static_idx", "static_names", "bound_kwargs",
                 "bound_positional")

    def __init__(self, call: ast.Call):
        self.call = call
        self.inner = call.args[0] if call.args else None
        self.donate_idx: Set[int] = set()
        self.donate_names: Set[str] = set()
        self.static_idx: Set[int] = set()
        self.static_names: Set[str] = set()
        self.bound_kwargs: Set[str] = set()      # functools.partial kwargs
        self.bound_positional = 0                # functools.partial args
        for kw in call.keywords:
            if kw.arg in ("donate_argnums", "donate_argnames"):
                tgt = (self.donate_idx if kw.arg == "donate_argnums"
                       else self.donate_names)
                _collect_const(kw.value, tgt)
            elif kw.arg in ("static_argnums", "static_argnames"):
                tgt = (self.static_idx if kw.arg == "static_argnums"
                       else self.static_names)
                _collect_const(kw.value, tgt)
        # unwrap functools.partial(fn, *bound, **bound_kw)
        if isinstance(self.inner, ast.Call):
            d = _call_name(self.inner)
            if d and d.split(".")[-1] == "partial" and self.inner.args:
                self.bound_positional = len(self.inner.args) - 1
                self.bound_kwargs = {kw.arg for kw in self.inner.keywords
                                     if kw.arg}
                self.inner = self.inner.args[0]


def _collect_const(node, out: Set):
    if isinstance(node, ast.Constant):
        out.add(node.value)
    elif isinstance(node, (ast.Tuple, ast.List, ast.Set)):
        for e in node.elts:
            if isinstance(e, ast.Constant):
                out.add(e.value)


def _jit_site(call) -> Optional[JitSite]:
    if not isinstance(call, ast.Call):
        return None
    d = _call_name(call)
    if d is None or d.split(".")[-1] not in _JIT_NAMES:
        return None
    # require jax.jit / bare jit — not e.g. self.jit
    if "." in d and d.split(".")[0] in ("self", "cls"):
        return None
    return JitSite(call)


# ---------------------------------------------------------------------------
# pass: use-after-donate
# ---------------------------------------------------------------------------


def _pass_use_after_donate(module: Module) -> List[Finding]:
    findings: List[Finding] = []
    # 1. donating callables: `<target> = jax.jit(fn, donate_argnums=...)`
    #    keyed by (owner_scope, dotted_target); owner_scope "" = module,
    #    "Class" = a `self._x` binding made inside that class.
    donating: Dict[Tuple[str, str], JitSite] = {}
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Assign):
            continue
        site = _jit_site(node.value)
        if site is None or (not site.donate_idx and not site.donate_names):
            continue
        for t in node.targets:
            tgt = _dotted(t)
            if tgt is None:
                continue
            scope = module.scope_of(node)
            if tgt.startswith("self."):
                owner = scope.rsplit(".", 1)[0] if "." in scope else ""
            else:
                owner = ""
            donating[(owner, tgt)] = site

    # 2. call sites of donating callables; donated arg bindings read later
    for qn, fn in module.functions():
        owner = qn.rsplit(".", 1)[0] if "." in qn else ""
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            callee = _dotted(node.func)
            site = None
            if callee is not None:
                site = donating.get((owner, callee)) \
                    or donating.get(("", callee))
            # immediate form: jax.jit(f, donate_argnums=...)(x)
            if site is None and isinstance(node.func, ast.Call):
                s = _jit_site(node.func)
                if s is not None and (s.donate_idx or s.donate_names):
                    site, callee = s, "jax.jit(...)"
            if site is None:
                continue
            donated: List[str] = []
            for i in site.donate_idx:
                if isinstance(i, int) and i < len(node.args):
                    d = _dotted(node.args[i])
                    if d is not None:
                        donated.append(d)
            for kw in node.keywords:
                if kw.arg in site.donate_names:
                    d = _dotted(kw.value)
                    if d is not None:
                        donated.append(d)
            if not donated:
                continue
            findings.extend(_donated_reads_after(
                module, fn, qn, node, callee, donated))
    return findings


def _donated_reads_after(module: Module, fn, qn: str, call: ast.Call,
                         callee: str, donated: List[str]) -> List[Finding]:
    out: List[Finding] = []
    anchor = _statement_of(call, module)
    if anchor is None:
        return out
    # the repaired idiom: the anchor statement rebinds the donated
    # binding from the call's results (`x, self.cache = f(self.cache)`)
    rebound_at_anchor: Set[str] = set()
    if isinstance(anchor, ast.Assign):
        for t in anchor.targets:
            for el in ([t] if not isinstance(t, (ast.Tuple, ast.List))
                       else t.elts):
                d = _dotted(el)
                if d is not None:
                    rebound_at_anchor.add(d)
    end = getattr(anchor, "end_lineno", anchor.lineno)
    # a donating call inside a loop also deletes the buffer for the NEXT
    # iteration: reads at lines before the call in the loop body execute
    # after the donation too. Reads in the OTHER arm of an ancestor `if`
    # are mutually exclusive with the call and can never follow it.
    loop = None
    excluded: Set[int] = set()
    child, cur = anchor, module.parents.get(anchor)
    while cur is not None and cur is not fn:
        if loop is None and isinstance(cur, (ast.For, ast.AsyncFor,
                                             ast.While)):
            loop = cur
        if isinstance(cur, ast.If):
            other = cur.orelse if _in_subtree(child, cur.body) else (
                cur.body if _in_subtree(child, cur.orelse) else [])
            for s in other:
                excluded.update(id(n) for n in ast.walk(s))
        child, cur = cur, module.parents.get(cur)
    for binding in donated:
        if binding in rebound_at_anchor:
            continue
        first_read = _hazard_read(fn, binding, lo=end, excluded=excluded)
        if first_read is None and loop is not None:
            first_read = _hazard_read(loop, binding, lo=loop.lineno,
                                      hi=anchor.lineno, excluded=excluded)
        if first_read is not None:
            line, col = first_read
            out.append(Finding(
                "use-after-donate", module.relpath, line, col, qn,
                f"{binding}@{callee}",
                f"read of `{binding}` after it was DONATED to "
                f"`{callee}(...)` at line {call.lineno} — the jit deleted "
                "that buffer; this read returns garbage or raises",
                hint="rebind it from the call's results "
                     f"(`..., {binding} = {callee}(...)`) or snapshot to "
                     "host BEFORE the donating call (the PR 3 "
                     "snapshot_state_dict fix)"))
    return out


def _hazard_read(scope, binding: str, lo: int, hi: Optional[int] = None,
                 excluded: Optional[Set[int]] = None
                 ) -> Optional[Tuple[int, int]]:
    """(line, col) of the first read of `binding` in `scope` within
    (lo, hi) that is not preceded by a rebind. A read on the SAME line
    as the first store still counts — the RHS of `m = fix(m)` executes
    before the store rebinds `m` (and `m += 1` reads m the same way).
    Nodes whose id is in `excluded` (mutually-exclusive branches) are
    skipped."""
    first_store = None
    first_read = None
    for node in ast.walk(scope):
        line = getattr(node, "lineno", None)
        if line is None or line <= lo or (hi is not None and line >= hi) \
                or (excluded and id(node) in excluded):
            continue
        if isinstance(node, ast.AugAssign) \
                and _dotted(node.target) == binding:
            # `m += 1` reads the deleted buffer before rebinding it
            if first_read is None or line < first_read[0]:
                first_read = (line, node.col_offset)
        if isinstance(node, (ast.Name, ast.Attribute)) \
                and _dotted(node) == binding:
            if isinstance(node.ctx, (ast.Store, ast.Del)):
                if first_store is None or line < first_store:
                    first_store = line
            elif first_read is None or line < first_read[0]:
                first_read = (line, node.col_offset)
    if first_read is not None and (first_store is None
                                   or first_read[0] <= first_store):
        return first_read
    return None


# ---------------------------------------------------------------------------
# pass: trace-hazard
# ---------------------------------------------------------------------------

_HOST_CONVERSIONS = {"float", "int", "bool", "complex"}
_HOST_MATERIALIZERS = {"np.asarray", "np.array", "numpy.asarray",
                       "numpy.array", "np.copy"}
_NONDET_CALLS = {"time.time", "time.perf_counter", "time.monotonic",
                 "time.time_ns", "datetime.now", "datetime.utcnow",
                 "datetime.datetime.now", "random.random", "random.randint",
                 "random.uniform", "random.choice", "uuid.uuid4"}
_NONDET_PREFIXES = ("np.random.", "numpy.random.")
_SHAPE_ATTRS = {"shape", "ndim", "dtype", "size", "aval"}


def _traced_functions(module: Module) -> Dict[ast.AST, Set[str]]:
    """Map traced FunctionDef -> static param names. Discovery: jit
    decorators, `x = jax.jit(fn_name, ...)` / `jax.jit(partial(fn_name,
    **static), ...)` assignments, and the registry's extras."""
    defs_by_name: Dict[str, List[ast.AST]] = {}
    for qn, fn in module.functions():
        defs_by_name.setdefault(fn.name, []).append(fn)
    traced: Dict[ast.AST, Set[str]] = {}

    def static_names_for(fn, site: JitSite) -> Set[str]:
        args = fn.args
        pos = [a.arg for a in args.posonlyargs + args.args]
        statics = set(site.static_names) | set(site.bound_kwargs)
        # kwonly params are static by repo convention (bound via partial
        # at the jit site: `partial(_mlp_ragged_stack, block_size=...)`)
        statics.update(a.arg for a in args.kwonlyargs)
        statics.update(registry.STATIC_PARAM_NAMES)
        for i in site.static_idx:
            if isinstance(i, int) and i < len(pos):
                statics.add(pos[i])
        statics.update(pos[:site.bound_positional])
        return statics

    for node in ast.walk(module.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                site = None
                if isinstance(dec, (ast.Name, ast.Attribute)):
                    d = _dotted(dec)
                    if d and d.split(".")[-1] in _JIT_NAMES:
                        site = JitSite(ast.Call(func=dec, args=[],
                                                keywords=[]))
                elif isinstance(dec, ast.Call):
                    d = _call_name(dec)
                    if d and d.split(".")[-1] in _JIT_NAMES:
                        site = JitSite(dec)
                    elif d and d.split(".")[-1] == "partial" and dec.args:
                        inner_d = _dotted(dec.args[0])
                        if inner_d and inner_d.split(".")[-1] in _JIT_NAMES:
                            site = JitSite(ast.Call(
                                func=dec.args[0], args=[],
                                keywords=dec.keywords))
                if site is not None:
                    traced[node] = static_names_for(node, site)
        site = _jit_site(node)
        if site is not None and site.inner is not None:
            if isinstance(site.inner, ast.Lambda):
                traced[site.inner] = set(registry.STATIC_PARAM_NAMES)
            else:
                d = _dotted(site.inner)
                if d is not None:
                    for fn in defs_by_name.get(d.split(".")[-1], []):
                        traced[fn] = static_names_for(fn, site)
    for sfx, qualname in registry.TRACED_FN_EXTRA:
        if registry._suffix_match(module.relpath, sfx):
            for qn, fn in module.functions():
                if qn == qualname:
                    traced.setdefault(fn, set(registry.STATIC_PARAM_NAMES))
    return traced


def _pass_trace_hazard(module: Module) -> List[Finding]:
    findings: List[Finding] = []
    for fn, statics in _traced_functions(module).items():
        if isinstance(fn, ast.Lambda):
            qn = module.scope_of(fn) + ".<lambda>"
            params = {a.arg for a in fn.args.args}
        else:
            qn = module.qualname.get(fn, fn.name)
            a = fn.args
            params = {x.arg for x in a.posonlyargs + a.args + a.kwonlyargs}
        traced_params = params - statics

        def flag(node, symbol, message, hint):
            findings.append(Finding("trace-hazard", module.relpath,
                                    node.lineno, node.col_offset, qn,
                                    symbol, message, hint))

        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                d = _call_name(node)
                if d in _HOST_CONVERSIONS and node.args \
                        and not _shape_like(node.args[0]) \
                        and not _static_expr(node.args[0], statics):
                    flag(node, f"{d}()",
                         f"`{d}()` on a traced value forces a host sync "
                         "(ConcretizationError under jit, a blocking "
                         "device fetch under lazy/eager)",
                         "keep the value on device (jnp ops) or hoist the "
                         "conversion out of the traced function")
                elif isinstance(node.func, ast.Attribute) \
                        and node.func.attr in ("item", "tolist") \
                        and not node.args \
                        and not _static_expr(node.func.value, statics):
                    flag(node, f".{node.func.attr}()",
                         f"`.{node.func.attr}()` inside a traced function "
                         "is a host sync per call",
                         "return the array and convert outside the jit")
                elif d in _HOST_MATERIALIZERS and not (
                        node.args and _static_expr(node.args[0], statics)):
                    flag(node, d,
                         f"`{d}` materializes a traced value on host "
                         "(silent device round-trip per call)",
                         "use jnp inside traced code; np belongs outside "
                         "the jit boundary")
                elif d and (d in _NONDET_CALLS
                            or d.startswith(_NONDET_PREFIXES)):
                    flag(node, d,
                         f"`{d}` runs at TRACE time — one draw/timestamp "
                         "is baked into the compiled program forever",
                         "thread randomness through jax.random keys / "
                         "pass timestamps as arguments")
            elif isinstance(node, (ast.If, ast.While)):
                name = _traced_name_in_test(node.test, traced_params)
                if name is not None:
                    kind = "if" if isinstance(node, ast.If) else "while"
                    flag(node, f"{kind}:{name}",
                         f"data-dependent `{kind}` on traced value "
                         f"`{name}` — Python control flow runs at trace "
                         "time and cannot branch on device data",
                         "use jnp.where / lax.cond / lax.while_loop, or "
                         "mark the argument static")
    return findings


def _static_expr(node, statics: Set[str]) -> bool:
    """True when the expression reads ONLY declared-static parameters
    (`float(block_size)` where block_size is partial-bound / kwonly /
    registry-static is trace-time arithmetic, not a host sync). Any call
    or non-static name makes it (conservatively) traced."""
    names = [n for n in ast.walk(node) if isinstance(n, ast.Name)
             and isinstance(n.ctx, ast.Load)]
    if not names or any(isinstance(n, ast.Call) for n in ast.walk(node)):
        return False
    return all(n.id in statics for n in names)


def _shape_like(node) -> bool:
    """True when the expression only touches trace-safe metadata
    (shapes, dtypes, len(), constants)."""
    if isinstance(node, ast.Constant):
        return True
    for n in ast.walk(node):
        if isinstance(n, ast.Attribute) and n.attr in _SHAPE_ATTRS:
            return True
        if isinstance(n, ast.Call):
            d = _call_name(n)
            if d in ("len", "isinstance", "getattr", "hasattr"):
                return True
    return False


def _traced_name_in_test(test, traced_params: Set[str]) -> Optional[str]:
    for n in ast.walk(test):
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load) \
                and n.id in traced_params:
            parent_ok = False
            # allowed: x.shape / x.ndim / x.dtype / len(x) / isinstance(x)
            # — detected structurally by re-walking for wrapping nodes
            for w in ast.walk(test):
                if isinstance(w, ast.Attribute) and w.value is n \
                        and w.attr in _SHAPE_ATTRS:
                    parent_ok = True
                if isinstance(w, ast.Call) and n in w.args:
                    d = _call_name(w)
                    if d in ("len", "isinstance", "getattr", "hasattr"):
                        parent_ok = True
                # `x is None` / `x is not None`: None is pytree
                # structure, never a tracer — resolved at trace time
                if isinstance(w, ast.Compare) and len(w.ops) == 1 \
                        and isinstance(w.ops[0], (ast.Is, ast.IsNot)) \
                        and (w.left is n or w.comparators[0] is n) \
                        and any(isinstance(s, ast.Constant)
                                and s.value is None
                                for s in (w.left, w.comparators[0])):
                    parent_ok = True
            if not parent_ok:
                return n.id
    return None


# ---------------------------------------------------------------------------
# pass: hot-path
# ---------------------------------------------------------------------------

_DEVICE_TRANSFER_CALLS = {"jnp.asarray", "jnp.array", "jax.device_put",
                          "device_put"}
_MONITOR_WRITES = {"inc", "set_gauge", "set_max", "set_value", "observe",
                   "histogram"}
_BLOCKING_CALLS = {"time.sleep", "os.system", "os.makedirs", "open",
                   "print", "json.dump", "json.load", "json.dumps"}
_BLOCKING_PREFIXES = ("subprocess.", "shutil.", "socket.")


def _pass_hot_path(module: Module) -> List[Finding]:
    findings: List[Finding] = []
    for qn, fn in module.functions():
        if not (registry.is_hot_path(module.relpath, qn)
                or module.has_pragma(fn, "hot-path")):
            continue

        def flag(node, symbol, message, hint):
            findings.append(Finding("hot-path", module.relpath,
                                    node.lineno, node.col_offset, qn,
                                    symbol, message, hint))

        for node in _walk_excluding_nested_defs(fn):
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                names = ",".join(a.name for a in node.names)
                flag(node, f"import:{names}",
                     f"per-call import of `{names}` on a declared hot "
                     "path (a dict lookup + lock every call)",
                     "hoist the import to module scope")
            elif isinstance(node, ast.Call):
                d = _call_name(node)
                if d is None:
                    continue
                tail = d.split(".")[-1]
                if d in _DEVICE_TRANSFER_CALLS:
                    flag(node, d,
                         f"per-call `{d}` on a declared hot path — a "
                         "host-side device_put per call (~1 ms/arg, "
                         "PR 10 measurement)",
                         "build exact-dtype numpy once and pass it raw; "
                         "the C++ dispatch path transfers it (see "
                         "serving/engine.py prefill)")
                elif tail in _MONITOR_WRITES and d.split(".")[0] in (
                        "monitor", "_monitor") and not _is_gated(
                            node, module, fn):
                    flag(node, f"{d.split('.')[0]}.{tail}",
                         f"unguarded `{d}` write on a declared hot path",
                         "route it through the ServingMetrics hooks or "
                         "gate it behind `observability.enabled()`")
                elif d in _BLOCKING_CALLS or d.startswith(
                        _BLOCKING_PREFIXES):
                    flag(node, d,
                         f"blocking call `{d}` on a declared hot path",
                         "move I/O off the per-token path (flight "
                         "recorder / deferred dump patterns)")
    return findings


def _walk_excluding_nested_defs(fn) -> Iterable[ast.AST]:
    """The statements executed per call: nested def/lambda bodies are
    cold closures (fault probes, rollbacks) and stay out of the hot
    per-call surface."""
    stack = list(ast.iter_child_nodes(fn))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.Lambda)):
            stack.extend(ast.iter_child_nodes(node))


# ---------------------------------------------------------------------------
# pass: zero-cost-off
# ---------------------------------------------------------------------------


def _producer_match(dotted: str) -> Optional[str]:
    segs = dotted.split(".")
    for p in registry.OBS_PAYLOAD_PRODUCERS:
        pseg = p.split(".")
        if segs[-len(pseg):] == pseg:
            return p
    return None


def _pass_zero_cost_off(module: Module) -> List[Finding]:
    if "/observability/" in f"/{module.relpath}":
        return []          # the sink itself; its internals ARE the layer
    findings: List[Finding] = []
    # gated-callees declared in this module (registry or pragma): their
    # bodies are exempt, calls TO them are payload sites
    gated_defs: Set[ast.AST] = set()
    gated_names: Set[str] = set()
    for qn, fn in module.functions():
        if registry.is_gated_callee(module.relpath, qn) \
                or module.has_pragma(fn, "gated-callee"):
            gated_defs.add(fn)
            gated_names.add(fn.name)
    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue
        d = _call_name(node)
        if d is None:
            continue
        producer = _producer_match(d)
        tail = d.split(".")[-1]
        if producer is None and tail in gated_names \
                and d.split(".")[0] in ("self", "cls"):
            producer = tail
        elif producer is None and d in gated_names:
            producer = d
        elif producer is None and tail in registry.GATED_CALLEE_NAMES:
            # registry-declared gated callee called from ANOTHER module
            # (imported helper): the "callers own the gate" contract
            # follows the name across module boundaries
            producer = tail
        if producer is None:
            continue
        fn = module.enclosing_function(node)
        enc = fn
        while enc is not None and enc not in gated_defs:
            enc = module.enclosing_function(enc)
        if enc is not None:
            continue       # body documented as caller-gated — a helper
                           # closure nested in it is part of that body
        if _is_gated(node, module, fn):
            continue
        qn = module.scope_of(node)
        findings.append(Finding(
            "zero-cost-off", module.relpath, node.lineno, node.col_offset,
            qn, producer,
            f"observability payload site `{d}` is not gated behind the "
            "enable bool — the zero-cost-off contract (PR 7) requires "
            "`if observability.enabled():` BEFORE any payload/timestamp "
            "is built",
            hint="wrap the site in `if _obs.enabled():` (or declare the "
                 "enclosing function `# ptlint: gated-callee` and gate "
                 "its callers)"))
    return findings


# ---------------------------------------------------------------------------
# pass: lock-hygiene
# ---------------------------------------------------------------------------

_LOCK_FACTORIES = {"Lock", "RLock", "Condition", "Semaphore",
                   "BoundedSemaphore"}
_MUTATOR_METHODS = {"append", "appendleft", "pop", "popleft", "popitem",
                    "clear", "update", "add", "remove", "discard",
                    "extend", "insert", "setdefault", "__setitem__"}
_BLOCKING_UNDER_LOCK = {"time.sleep", "sleep"}


def _pass_lock_hygiene(module: Module) -> List[Finding]:
    if not registry.is_threaded_module(module.relpath):
        return []
    findings: List[Finding] = []
    locks: Set[str] = set()
    for node in ast.walk(module.tree):
        if isinstance(node, ast.Assign) and isinstance(node.value, ast.Call):
            d = _call_name(node.value)
            if d and d.split(".")[-1] in _LOCK_FACTORIES:
                for t in node.targets:
                    td = _dotted(t)
                    if td is not None:
                        locks.add(td)
    if not locks:
        return []

    def lock_withs(scope):
        for node in ast.walk(scope):
            if isinstance(node, ast.With):
                for item in node.items:
                    d = _dotted(item.context_expr)
                    if d in locks:
                        yield node, d

    # 1. which state is lock-protected anywhere in the module
    guarded_state: Set[str] = set()
    guarded_nodes: Set[ast.AST] = set()
    for wnode, _lk in lock_withs(module.tree):
        for inner in ast.walk(wnode):
            guarded_nodes.add(inner)
            base = _mutated_base(inner)
            if base is not None:
                guarded_state.add(base)
    # the locks themselves aren't "state"
    guarded_state -= locks

    # 2. findings
    for node in ast.walk(module.tree):
        base = _mutated_base(node)
        if base is not None and base in guarded_state \
                and node not in guarded_nodes:
            fn = module.enclosing_function(node)
            qn = module.qualname.get(fn, "") if fn is not None else ""
            if qn.split(".")[-1] in ("__init__", "__new__") or fn is None:
                continue   # construction happens-before sharing
            findings.append(Finding(
                "lock-hygiene", module.relpath, node.lineno,
                node.col_offset, qn, f"unguarded-write:{base}",
                f"`{base}` is mutated under a lock elsewhere in this "
                "module but written here WITHOUT holding it",
                hint="take the same `with <lock>:` around this write, or "
                     "move the mutation into the locked helper"))
        if node in guarded_nodes and isinstance(node, ast.Call):
            d = _call_name(node)
            if d is None:
                continue
            is_join = isinstance(node.func, ast.Attribute) \
                and node.func.attr == "join" \
                and not isinstance(node.func.value, ast.Constant) \
                and not node.args
            if d in _BLOCKING_UNDER_LOCK or is_join \
                    or d.startswith("subprocess."):
                fn = module.enclosing_function(node)
                qn = module.qualname.get(fn, "") if fn is not None else ""
                sym = "join()" if is_join else d
                findings.append(Finding(
                    "lock-hygiene", module.relpath, node.lineno,
                    node.col_offset, qn, f"blocking-under-lock:{sym}",
                    f"`{sym}` while holding a lock — every other thread "
                    "contending on it stalls for the full wait",
                    hint="drop the lock before sleeping/joining (claim "
                         "under the lock, wait outside — see "
                         "save_state_dict's drain loop)"))
    return findings


def _mutated_base(node) -> Optional[str]:
    """Dotted base of a mutation: `X[...] = / X.attr = / X.append(...)`.
    Returns None for non-mutations and for plain-Name rebinds (locals)."""
    if isinstance(node, ast.Assign):
        for t in node.targets:
            b = _store_base(t)
            if b is not None:
                return b
    elif isinstance(node, (ast.AugAssign, ast.AnnAssign)):
        return _store_base(node.target)
    elif isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute):
        if node.func.attr in _MUTATOR_METHODS:
            return _dotted(node.func.value)
    elif isinstance(node, ast.Delete):
        for t in node.targets:
            b = _store_base(t)
            if b is not None:
                return b
    return None


def _store_base(t) -> Optional[str]:
    if isinstance(t, ast.Subscript):
        return _dotted(t.value)
    if isinstance(t, ast.Attribute):
        return _dotted(t)        # self._x = ... -> "self._x"
    return None                  # bare Name rebind: a local, not shared


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

_ALL_PASSES = (
    ("use-after-donate", _pass_use_after_donate),
    ("trace-hazard", _pass_trace_hazard),
    ("hot-path", _pass_hot_path),
    ("zero-cost-off", _pass_zero_cost_off),
    ("lock-hygiene", _pass_lock_hygiene),
)


def scan_file(path: str, relpath: str,
              passes: Optional[Iterable[str]] = None) -> List[Finding]:
    try:
        with open(path, encoding="utf-8") as f:
            source = f.read()
        module = Module(path, relpath, source)
    except (SyntaxError, UnicodeDecodeError, ValueError) as e:
        line = getattr(e, "lineno", 0) or 0
        msg = getattr(e, "msg", None) or str(e)
        return [Finding("parse-error", relpath, line, 0, "",
                        "syntax", f"cannot parse: {msg}")]
    wanted = set(passes) if passes is not None else None
    def_line = {qn: fn.lineno for qn, fn in module.functions()}

    def pragma_disabled(finding: Finding) -> bool:
        lines = [finding.line, def_line.get(finding.scope)]
        for line in lines:
            for p in module.pragmas.get(line or -1, []):
                if p.startswith("disable=") and finding.pass_id in \
                        p.split("=", 1)[1].split(","):
                    return True
        return False

    out: List[Finding] = []
    for pass_id, fn in _ALL_PASSES:
        if wanted is not None and pass_id not in wanted:
            continue
        out.extend(f for f in fn(module) if not pragma_disabled(f))
    out.sort(key=lambda f: (f.path, f.line, f.pass_id))
    return out


def collect_files(root: str, targets: Iterable[str]) -> List[Tuple[str, str]]:
    """(abspath, relpath) for every .py under the target dirs/files."""
    out: List[Tuple[str, str]] = []
    for target in targets:
        ab = target if os.path.isabs(target) else os.path.join(root, target)
        ab = os.path.abspath(ab)
        if os.path.isfile(ab):
            out.append((ab, os.path.relpath(ab, root).replace(os.sep, "/")))
            continue
        if not os.path.isdir(ab):
            raise FileNotFoundError(f"ptlint target not found: {target}")
        for dirpath, dirnames, filenames in os.walk(ab):
            dirnames[:] = [d for d in dirnames
                           if d != "__pycache__" and not d.startswith(".")]
            for name in sorted(filenames):
                if name.endswith(".py"):
                    p = os.path.join(dirpath, name)
                    out.append((p, os.path.relpath(p, root).replace(
                        os.sep, "/")))
    seen = set()
    uniq = []
    for ab, rel in sorted(out, key=lambda x: x[1]):
        if rel not in seen:
            seen.add(rel)
            uniq.append((ab, rel))
    return uniq


def scan_paths(root: str, targets: Iterable[str],
               passes: Optional[Iterable[str]] = None
               ) -> Tuple[List[Finding], List[str]]:
    """Run tier A over the targets. Returns (findings, scanned relpaths)."""
    files = collect_files(root, targets)
    findings: List[Finding] = []
    for ab, rel in files:
        findings.extend(scan_file(ab, rel, passes))
    return findings, [rel for _ab, rel in files]
