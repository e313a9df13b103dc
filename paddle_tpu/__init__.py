"""paddle_tpu — a TPU-native deep-learning framework with PaddlePaddle's capability
surface, built from scratch on JAX/XLA/Pallas/PJRT (see SURVEY.md at the repo root).

Eager mode: Tensor over PJRT buffers + tape autograd over jit-cached per-op executables.
Graph mode: whole-program XLA via `paddle_tpu.jit.to_static`.
Distributed: GSPMD over `jax.sharding.Mesh` (dp/mp/pp/sep/sharding/ep axes).
"""
from __future__ import annotations

import time as _time

_import_began = _time.time()    # `startup.import_s`: this line to the last

import os as _os  # noqa: E402

import jax as _jax  # noqa: E402

# the compile record listens to JAX from here on
from .observability import compile_trace as _compile_trace  # noqa: E402

_compile_trace.install()

# Multi-process rendezvous must happen BEFORE anything initialises the XLA
# backend, and importing this package touches devices (Tensor machinery), so
# the launch env contract (PADDLE_MASTER et al., written by
# `paddle_tpu.distributed.launch`) is honoured at import time — the worker
# side of SURVEY.md §3.4 step 3.
if int(_os.environ.get("PADDLE_TRAINERS_NUM", "1")) > 1 \
        and _os.environ.get("PADDLE_MASTER"):
    def _coordination_up() -> bool:
        try:
            from jax._src import distributed as _jdist

            return _jdist.global_state.client is not None
        except Exception:
            return False

    if not _coordination_up():
        # a rendezvous FAILURE must crash the worker (silently dropping to
        # single-process would train on divergent weights); only skip when
        # the service is already up
        _jax.distributed.initialize(
            coordinator_address=_os.environ["PADDLE_MASTER"],
            num_processes=int(_os.environ["PADDLE_TRAINERS_NUM"]),
            process_id=int(_os.environ.get("PADDLE_TRAINER_ID", "0")))

# int64/float64 semantics to match the reference's default dtypes (indices are
# int64, paddle.arange of ints is int64). Float ops stay float32/bf16 unless the
# user asks for float64.
_jax.config.update("jax_enable_x64", True)

from .framework import dtype as _dtype_mod  # noqa: E402
from .framework.dtype import (DType, bfloat16, complex64, complex128,  # noqa: E402
                              float8_e4m3fn, float8_e5m2, float16, float32,
                              float64, get_default_dtype, int8, int16, int32,
                              int64, set_default_dtype, uint8)
from .framework.dtype import bool_ as bool  # noqa: E402
from .framework.place import (CPUPlace, CUDAPinnedPlace, CUDAPlace, Place,  # noqa: E402
                              TPUPlace, device_count, get_device,
                              is_compiled_with_cuda, is_compiled_with_tpu,
                              set_device)
from .framework.flags import get_flags, set_flags  # noqa: E402
from .framework.random import get_rng_state, seed, set_rng_state  # noqa: E402
from .core.tensor import Tensor  # noqa: E402
from .core.autograd import (enable_grad, grad, is_grad_enabled, no_grad,  # noqa: E402
                            set_grad_enabled)
from . import ops as _ops  # noqa: E402  (patches Tensor methods)
from .ops import *  # noqa: F401,F403,E402
from .ops import cast, matmul, reshape, concat  # noqa: E402

__version__ = "0.1.0"

# Subsystem imports. A missing module (not yet built) is tolerated; an
# ImportError raised INSIDE an existing module is a real bug and propagates —
# the silent `except ImportError: pass` loop hid those (round-2 VERDICT).
import importlib.util as _ilu  # noqa: E402

for _mod in ("nn", "optimizer", "amp", "io", "jit", "static", "metric", "vision",
             "distributed", "autograd", "hapi", "incubate", "profiler",
             "distribution", "fft", "sparse", "quantization", "onnx", "utils",
             "device", "inference", "serving", "resilience", "signal",
             "audio", "text", "geometric", "hub", "sysconfig"):
    if _ilu.find_spec(f"{__name__}.{_mod}") is not None:
        __import__(f"{__name__}.{_mod}")

from .framework.io import load, save  # noqa: E402

if _ilu.find_spec(f"{__name__}.hapi") is not None:
    from .hapi.model import Model, summary  # noqa: E402

# remaining top-level parity surface (reference python/paddle/__init__.py)
from .nn.parameter import ParamAttr, create_parameter  # noqa: E402
from .distributed.parallel import DataParallel  # noqa: E402
from .framework.dtype import DType as dtype  # noqa: E402
from .utils.flops import flops  # noqa: E402

# CUDA-named RNG state APIs map to the accelerator generator (framework/random.py)
get_cuda_rng_state = get_rng_state
set_cuda_rng_state = set_rng_state

# Tensor-method parity: bind every reference tensor_method_func name that
# is not yet a Tensor attribute to the same-named free function (reference
# `tensor/__init__.py` does the identical setattr loop).
from .tensor_method_names import TENSOR_METHOD_NAMES as _TM_NAMES  # noqa: E402


def _bind_tensor_methods():
    import sys as _sys

    me = _sys.modules[__name__]
    search = [me]
    for sub in ("linalg", "fft", "signal", "geometric"):
        m = getattr(me, sub, None)
        if m is not None:
            search.append(m)
    for name in _TM_NAMES:
        if hasattr(Tensor, name):
            continue
        for mod in search:
            fn = getattr(mod, name, None)
            if callable(fn) and not isinstance(fn, type):
                setattr(Tensor, name, fn)
                break


_bind_tensor_methods()


class LazyGuard:
    """Compatibility context (reference nn/initializer/lazy_init.py): defers
    parameter materialization. Under XLA, initializer programs are traced jit
    functions whose buffers materialize on first device use, so eager Python
    work inside the guard is already minimal; this guard is a no-op marker."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def disable_static(*a, **k):
    from . import static as _s

    return _s.disable_static()


def enable_static(*a, **k):
    from . import static as _s

    return _s.enable_static()


def in_dynamic_mode() -> bool:
    try:
        from . import static as _s

        return not _s.in_static_mode()
    except Exception:
        return True


_compile_trace.stamp("startup.import", _import_began)
