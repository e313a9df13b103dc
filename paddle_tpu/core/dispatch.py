"""Eager op dispatch with per-op executable caching.

TPU-native analog of the reference dispatch path (§3.1 of SURVEY.md): Python op →
generated C binding → ad_func → kernel selection (`phi/core/kernel_factory.cc:270`) →
CUDA kernel launch. On TPU the "kernel" is an XLA executable, so dispatch is a cache
lookup ``(op, static attrs, input shapes/dtypes, grad mask) -> compiled callable``; a miss
traces the op's JAX function and compiles it once (SURVEY.md §7.2 M1).

When grad is required the cached callable is ``jit(lambda *xs: jax.vjp(fn, *xs))`` — one
compiled program that returns both outputs and the residual-carrying ``vjp_fn`` pytree,
which the autograd node replays later (the analog of the generated GradNode capturing
TensorWrappers, `fluid/eager/eager_gen.py:1127`).

Inside an outer trace (graph mode / jax transforms) dispatch degrades to a plain function
call on tracers with no tape recording, so the same eager API is traceable by `to_static`.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from ..framework import flags
from ..framework.dtype import is_inexact_np
from . import autograd, lazy

_OP_REGISTRY: Dict[str, "OpDef"] = {}

# AMP input-rewrite hook installed by paddle_tpu.amp (the analog of the
# auto-cast logic codegen injects into every ad_func, `eager_gen.py:1887`).
_amp_hook: Optional[Callable] = None
# observers fed (op_name, out_tensors) — used by amp.debugging op-stats.
_op_observers: list = []


def set_amp_hook(fn: Optional[Callable]):
    global _amp_hook
    _amp_hook = fn


def add_op_observer(fn: Callable):
    _op_observers.append(fn)


def remove_op_observer(fn: Callable):
    if fn in _op_observers:
        _op_observers.remove(fn)


class OpDef:
    """One operator: a pure JAX function ``fn(*arrays, **attrs)``.

    Analog of one entry in the reference's `phi/ops/yaml/ops.yaml` — name, callable
    kernel, and autodiff participation. ``multi_out`` marks tuple-returning ops.
    """

    __slots__ = ("name", "fn", "multi_out")

    def __init__(self, name: str, fn: Callable, multi_out: bool = False):
        self.name = name
        self.fn = fn
        self.multi_out = multi_out


def register_op(name: str, fn: Callable = None, *, multi_out: bool = False):
    """Register an op. Usable as decorator or direct call."""

    def deco(f):
        _OP_REGISTRY[name] = OpDef(name, f, multi_out=multi_out)
        return f

    if fn is not None:
        return deco(fn)
    return deco


def get_op(name: str) -> OpDef:
    return _OP_REGISTRY[name]


def op_registry() -> Dict[str, OpDef]:
    return _OP_REGISTRY


# ---------------------------------------------------------------------------
# Executable caches
# ---------------------------------------------------------------------------

_fwd_cache: Dict[tuple, Callable] = {}
_fwd_vjp_cache: Dict[tuple, Callable] = {}
_fwd_grad_cache: Dict[tuple, Callable] = {}

_compile_count = 0


def cache_stats():
    return {"fwd": len(_fwd_cache), "fwd_vjp": len(_fwd_vjp_cache),
            "fwd_grad": len(_fwd_grad_cache), "compiles": _compile_count}


def clear_caches():
    _fwd_cache.clear()
    _fwd_vjp_cache.clear()
    _fwd_grad_cache.clear()


def _canon_attr(v):
    if isinstance(v, (list, tuple)):
        return tuple(_canon_attr(x) for x in v)
    if isinstance(v, dict):
        return tuple(sorted((k, _canon_attr(x)) for k, x in v.items()))
    if isinstance(v, np.ndarray):
        return ("__np__", v.tobytes(), v.shape, str(v.dtype))
    return v


def _attr_key(attrs: dict) -> tuple:
    if not attrs:
        return ()
    return tuple(sorted((k, _canon_attr(v)) for k, v in attrs.items()))


def _aval_key(arrays) -> tuple:
    # hot path: np.dtype objects hash/compare fine — no str() conversion
    return tuple(None if a is None else (a.shape, a.dtype) for a in arrays)


@functools.lru_cache(maxsize=1)
def _jax():
    import jax

    return jax


def _is_tracer(x) -> bool:
    return isinstance(x, _tracer_cls())


@functools.lru_cache(maxsize=1)
def _tracer_cls():
    return _jax().core.Tracer


def _log_compile(kind, name, key):
    global _compile_count
    _compile_count += 1
    from ..framework import monitor

    monitor.inc(f"dispatch.compiles.{kind}")
    if flags.flag_value("log_compiles"):
        print(f"[paddle_tpu] compile {kind} op={name}")


def _obs_trace_compile(cache, key, fn, kind, name):
    """Observability hook on an executable-cache miss: diff the structure
    key against the nearest cached signature of the same op (the retrace
    cause) and, after the FIRST call — trace+compile happen lazily there —
    attach kind, op, key and cause to the record JAX's own events made of
    it (`observability.compile_trace`). The wrapper swaps the raw jitted
    fn back into the cache after that call, so steady-state dispatch pays
    nothing. No-op (returns `fn` unwrapped) while observability is
    disabled — the cold compile path is the only place this is even
    consulted."""
    from .. import observability as _obs

    if not _obs.enabled():
        return fn
    cause = _obs.compile_trace.on_compile(kind, name, key)

    def first_call(*args, **kw):
        since = _obs.compile_trace.mark()
        out = fn(*args, **kw)
        _obs.compile_trace.attach(since, kind=kind, op=name, key=key,
                                  cause=cause)
        cache[key] = fn
        return out

    return first_call


def _evict(cache: dict):
    """Bound cache size to FLAGS_eager_cache_size (FIFO eviction)."""
    limit = flags.flag_value("eager_cache_size")
    while len(cache) >= limit > 0:
        cache.pop(next(iter(cache)))


def _get_fwd(op: OpDef, attrs: dict, arrays) -> Callable:
    jax = _jax()
    key = (op.name, _attr_key(attrs), _aval_key(arrays))
    fn = _fwd_cache.get(key)
    if fn is None:
        _evict(_fwd_cache)
        _log_compile("fwd", op.name, key)
        base = op.fn
        if attrs:
            base = functools.partial(base, **attrs)
        fn = _obs_trace_compile(_fwd_cache, key, jax.jit(base), "fwd",
                                op.name)
        _fwd_cache[key] = fn
    return fn


def _get_fwd_vjp(op: OpDef, attrs: dict, arrays, mask) -> Callable:
    jax = _jax()
    key = (op.name, _attr_key(attrs), _aval_key(arrays), mask)
    fn = _fwd_vjp_cache.get(key)
    if fn is None:
        _evict(_fwd_vjp_cache)
        _log_compile("fwd_vjp", op.name, key)
        base = op.fn
        if attrs:
            base = functools.partial(base, **attrs)

        def fwd(*arrays, _base=base, _mask=mask):
            # stop_gradient on inputs that don't require grad so the vjp does
            # no wasted transpose work for them.
            prims = [a if m else jax.lax.stop_gradient(a)
                     for a, m in zip(arrays, _mask)]
            out, vjp_fn = jax.vjp(lambda *xs: _base(*xs), *prims)
            return out, vjp_fn

        fn = _obs_trace_compile(_fwd_vjp_cache, key, jax.jit(fwd),
                                "fwd_vjp", op.name)
        _fwd_vjp_cache[key] = fn
    return fn


def _get_fwd_grad(op: OpDef, attrs: dict, arrays, mask, seed_slots,
                  seed_arrays) -> Callable:
    """One executable computing BOTH the op's outputs and its gradients
    w.r.t. masked inputs, with runtime seed cotangents added at
    `seed_slots` of the (tuple) outputs. The lazy tracer's `backward()`
    fast path: the whole fused region's fwd+bwd is a single XLA program
    (no residual materialization between them)."""
    jax = _jax()
    key = (op.name, _attr_key(attrs), _aval_key(arrays), mask,
           tuple(seed_slots), _aval_key(seed_arrays))
    fn = _fwd_grad_cache.get(key)
    if fn is None:
        _evict(_fwd_grad_cache)
        _log_compile("fwd_grad", op.name, key)
        base = op.fn
        if attrs:
            base = functools.partial(base, **attrs)
        n_in = len(arrays)

        def fwd_grad(*args, _base=base, _mask=mask, _n=n_in,
                     _slots=tuple(seed_slots)):
            xs, seeds = args[:_n], args[_n:]
            prims = [a if m else jax.lax.stop_gradient(a)
                     for a, m in zip(xs, _mask)]
            # vjp over the SEEDED outputs only — unseeded outputs (logits
            # kept alive by the user, metrics, ...) ride along as aux from
            # the SAME forward pass and contribute no backward work.
            def f(*p):
                o = tuple(_base(*p))
                return tuple(o[s] for s in _slots), o

            souts, vjp_fn, outs = jax.vjp(f, *prims, has_aux=True)
            cts = [s.astype(o.dtype) for s, o in zip(seeds, souts)]
            grads = vjp_fn(tuple(cts))
            # only mask-True slots carry real gradients; dropping the rest
            # avoids materializing zero / float0 outputs (float0 also knocks
            # the call off the pjit fast path)
            grads = tuple(g for g, m in zip(grads, _mask) if m)
            return outs, grads

        fn = _obs_trace_compile(_fwd_grad_cache, key, jax.jit(fwd_grad),
                                "fwd_grad", op.name)
        _fwd_grad_cache[key] = fn
    return fn


@functools.lru_cache(maxsize=1)
def _vjp_caller():
    jax = _jax()

    jitted = jax.jit(lambda vf, ct: vf(ct))

    def call(vjp_fn, ct):
        try:
            return jitted(vjp_fn, ct)
        except Exception:
            return vjp_fn(ct)

    return call


# ---------------------------------------------------------------------------
# The eager entry point
# ---------------------------------------------------------------------------


def _differentiable(a) -> bool:
    return a is not None and is_inexact_np(a.dtype)


# Profiler hook: when set, every eager op dispatch is timed and reported as
# (op_name, t_start, t_end) — the host-span source for paddle.profiler
# (reference analog: RecordOpInfoSupplement in the host tracer).
_profile_cb: Optional[Callable] = None


def set_profile_hook(fn: Optional[Callable]):
    global _profile_cb
    _profile_cb = fn


def apply(op_name: str, tensor_inputs: Sequence, attrs: Optional[dict] = None):
    """Run one op on Tensor inputs; returns Tensor or list of Tensors.

    The eager hot loop (§3.1 steps 2-7 of SURVEY.md collapsed into one cache hit).
    """
    if _profile_cb is not None:
        import time as _time

        t0 = _time.perf_counter()
        out = _apply(op_name, tensor_inputs, attrs)
        _profile_cb(op_name, t0, _time.perf_counter())
        return out
    return _apply(op_name, tensor_inputs, attrs)


_Tensor = None


def _tensor_cls():
    global _Tensor
    if _Tensor is None:
        from .tensor import Tensor

        _Tensor = Tensor
    return _Tensor


def _apply(op_name: str, tensor_inputs: Sequence, attrs: Optional[dict] = None):
    Tensor = _Tensor or _tensor_cls()

    op = _OP_REGISTRY[op_name]
    attrs = attrs or {}
    if _amp_hook is not None:
        tensor_inputs = _amp_hook(op_name, tensor_inputs)

    # Lazy eager mode: record into the pending micro-graph instead of
    # executing (core/lazy.py); falls through to the immediate path when
    # recording declines (tracer inputs, aval-inference failure).
    if lazy.is_lazy_enabled():
        out = lazy.try_record(op, tensor_inputs, attrs)
        if out is not lazy._NOT_HANDLED:
            return out

    # One scan over the inputs: unwrap arrays, detect tracers, build the
    # per-slot differentiability mask (the reference folds this into the
    # generated ad_func prologue, `eager_gen.py:1887`).
    Tracer = _tracer_cls()
    arrays = []
    mask = []
    has_tracer = False
    any_live = False
    for t in tensor_inputs:
        if isinstance(t, Tensor):
            a = t._data
            if type(a) is lazy.LazyArray:
                # pending value consumed by a non-lazy dispatch: barrier
                a = a._concrete if a._concrete is not None \
                    else a.materialize()
            arrays.append(a)
            if isinstance(a, Tracer):
                has_tracer = True
            live = not t.stop_gradient
            if live:
                any_live = True
            mask.append(live and _differentiable(a))
        else:
            arrays.append(t)
            mask.append(False)
            if isinstance(t, Tracer):
                has_tracer = True

    # Graph-capture path: inside jax tracing there is no tape; call through.
    if has_tracer:
        out = op.fn(*arrays, **attrs)
        sg = not (autograd.is_grad_enabled() and any_live)
        return _wrap_traced(op, out, sg)

    requires = any(mask) and autograd.is_grad_enabled()

    if not requires:
        fn = _get_fwd(op, attrs, arrays)
        out = fn(*arrays)
        return _wrap(op, out, stop_gradient=True)

    mask = tuple(mask)
    fn = _get_fwd_vjp(op, attrs, arrays, mask)
    out, vjp_fn = fn(*arrays)

    out_is_tuple = isinstance(out, (tuple, list))
    outs = list(out) if out_is_tuple else [out]

    node = autograd.OpGradNode(op.name, len(outs), vjp_fn, mask, out_is_tuple,
                               _vjp_caller())
    node.out_avals = [(o.shape, o.dtype) for o in outs]
    # TensorWrapper analog (`fluid/eager/tensor_wrapper.h:39`): snapshot the
    # primal inputs + attrs so grad(create_graph=True) can re-execute this
    # node's backward as taped eager ops (vjp-of-vjp). Stored as
    # (data, grad_node, out_index, stop_gradient) tuples — the data array is
    # frozen at forward time (in-place set_value cannot corrupt the second
    # backward) and no strong ref to the user Tensor object is kept; cleared
    # by release() together with the vjp buffers.
    snap = []
    for t in tensor_inputs:
        if isinstance(t, Tensor):
            gn = t._grad_node
            oi = t._out_index
            if gn is None and not t.stop_gradient and _differentiable(t._data):
                gn, oi = t._ensure_accum_node(), 0
            snap.append(("__tensor__", t._data, gn, oi, t.stop_gradient))
        else:
            snap.append(t)
    node.primals = snap
    node.attrs = dict(attrs)
    for t in tensor_inputs:
        if isinstance(t, Tensor) and not t.stop_gradient and _differentiable(t._data):
            if t._grad_node is not None:
                node.edges.append((t._grad_node, t._out_index))
            else:
                node.edges.append((t._ensure_accum_node(), 0))
        else:
            node.edges.append(None)

    results = []
    for i, o in enumerate(outs):
        sg = not _differentiable(o)
        t = Tensor(o, stop_gradient=sg)
        if not sg:
            t._grad_node = node
            t._out_index = i
        node.out_hooks.append(t._hooks)
        results.append(t)

    _maybe_check_nan_inf(op.name, results)
    if not out_is_tuple:
        return results[0]
    return results


def apply_vjp(op_name: str, primal_inputs, attrs, ct_tensors, mask,
              out_is_tuple):
    """Differentiable backward of one op: runs `vjp(op)(cts)` THROUGH the
    eager dispatch layer, so the produced gradients carry their own grad
    nodes (the double-grad path, reference `fluid/eager/general_grad.h:38`).

    primal_inputs: the node's captured forward inputs (Tensors / raw);
    ct_tensors: per-output cotangents (Tensors, zero-filled by the caller).
    """
    meta_name = f"__vjp__{op_name}"
    if meta_name not in _OP_REGISTRY:
        base_fn = _OP_REGISTRY[op_name].fn
        register_op(meta_name, _make_generic_vjp(base_fn), multi_out=True)
    call_attrs = {f"__a_{k}": v for k, v in (attrs or {}).items()}
    call_attrs["__n"] = len(primal_inputs)
    call_attrs["__mask"] = tuple(mask)
    call_attrs["__tuple"] = bool(out_is_tuple)
    return apply(meta_name, list(primal_inputs) + list(ct_tensors),
                 call_attrs)


def _make_generic_vjp(base_fn):
    def generic_vjp(*arrays, **kw):
        jax = _jax()
        n = kw.pop("__n")
        mask = kw.pop("__mask")
        is_tuple = kw.pop("__tuple")
        op_attrs = {k[len("__a_"):]: v for k, v in kw.items()}
        primals = arrays[:n]
        cts = list(arrays[n:])
        f = functools.partial(base_fn, **op_attrs) if op_attrs else base_fn
        prims = [p if m else jax.lax.stop_gradient(p)
                 for p, m in zip(primals, mask)]
        out, vjp_fn = jax.vjp(lambda *xs: f(*xs), *prims)
        outs = list(out) if is_tuple else [out]
        from ..framework.dtype import is_inexact_np

        fixed = []
        for o, ct in zip(outs, cts):
            if not is_inexact_np(np.dtype(o.dtype)):
                # integer/bool outputs take symbolic-zero cotangents
                fixed.append(np.zeros(o.shape, jax.dtypes.float0))
            else:
                fixed.append(ct.astype(o.dtype) if ct.dtype != o.dtype
                             else ct)
        grads = vjp_fn(tuple(fixed) if is_tuple else fixed[0])
        # float0 grads (non-diff inputs) -> zeros so the op has uniform
        # array outputs; the autograd layer masks them out via in_mask
        clean = []
        for g, p in zip(grads, primals):
            if g is None or (hasattr(g, "dtype")
                             and g.dtype == jax.dtypes.float0):
                clean.append(jax.numpy.zeros(() if p is None
                                             else jax.numpy.shape(p)))
            else:
                clean.append(g)
        return tuple(clean)

    return generic_vjp


def _wrap(op, out, stop_gradient):
    from .tensor import Tensor

    if isinstance(out, (tuple, list)):
        res = [Tensor(o, stop_gradient=True) for o in out]
        _maybe_check_nan_inf(op.name, res)
        return res
    t = Tensor(out, stop_gradient=True)
    _maybe_check_nan_inf(op.name, [t])
    return t


def _wrap_traced(op, out, stop_gradient):
    from .tensor import Tensor

    if isinstance(out, (tuple, list)):
        return [Tensor(o, stop_gradient=stop_gradient) for o in out]
    return Tensor(out, stop_gradient=stop_gradient)


def _maybe_check_nan_inf(name, tensors):
    """FLAGS_check_nan_inf analog (`fluid/eager/nan_inf_utils.h:38`)."""
    for obs in _op_observers:
        obs(name, tensors)
    if not flags.flag_value("check_nan_inf"):
        return
    import jax.numpy as jnp

    for t in tensors:
        d = t._data
        from ..framework.dtype import is_inexact_np

        if is_inexact_np(d.dtype):
            bad = bool(jnp.logical_not(jnp.isfinite(d)).any())
            if bad:
                msg = f"Op {name} produced NaN/Inf in output {t.shape}"
                if flags.flag_value("check_nan_inf_level") == 0:
                    raise FloatingPointError(msg)
                print("[paddle_tpu][nan_inf]", msg)
