"""Shared gating/helpers for the Pallas TPU kernel library.

The kernels compile natively on TPU (Mosaic); off-TPU they run through the
Pallas interpreter when `FLAGS_pallas_interpret` is set (the test path on the
8-device CPU mesh), else callers fall back to the XLA composite ops.
"""
from __future__ import annotations

import functools

from ...framework import flags

flags.define_flag("use_pallas", True, "use Pallas kernels for fused ops on TPU")
flags.define_flag("pallas_interpret", False,
                  "run Pallas kernels in interpreter mode off-TPU (tests)")


@functools.lru_cache(maxsize=1)
def backend() -> str:
    import jax

    return jax.default_backend()


def on_tpu() -> bool:
    return backend() == "tpu"


def interpret_mode() -> bool:
    """True when kernels must run via the Pallas interpreter (non-TPU)."""
    return not on_tpu()


def kernels_enabled(*operands) -> bool:
    """The gate every kernel selection starts from. `operands`, where the
    caller has them, are arrays the kernel would take: one that lives on a
    multi-device mesh with automatic (GSPMD) axes closes the gate, because
    JAX refuses to lower a Mosaic kernel there — "Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map." — and
    the caller's XLA composite partitions fine. Inside `shard_map` the axes
    are Manual and the gate stays open (the TP serving step, the EP MoE
    body). A step jitted with `in_shardings` over UNCOMMITTED arguments
    shows no mesh while it traces; there the refusal stands, at lowering."""
    if any(_auto_partitioned(x) for x in operands):
        return False
    if on_tpu():
        return bool(flags.flag_value("use_pallas"))
    return bool(flags.flag_value("pallas_interpret"))


def _auto_partitioned(x) -> bool:
    import jax
    from jax.sharding import AxisType

    if not isinstance(x, jax.Array):     # numpy, a pending lazy-mode value
        return False
    mesh = jax.typeof(x).sharding.mesh
    return mesh.size > 1 and AxisType.Auto in mesh.axis_types


def x64_off():
    """Context manager disabling x64 around a `pallas_call` invocation.

    The package enables jax_enable_x64 globally (paddle int64 semantics), but
    Mosaic has no i64/f64: under x64, Python int literals in BlockSpec index
    maps and float scalars in kernel bodies trace as 64-bit and fail TPU
    lowering (infinite _convert_helper recursion / truncf legalization).
    Kernel dtypes are all explicit, so tracing them with x64 off is exact.
    """
    import jax

    return jax.enable_x64(False)


def pallas_call(*args, name: str, **kwargs):
    """`pl.pallas_call` whose returned callable traces with x64 disabled.

    All kernels in this package must go through this wrapper (see x64_off).
    `name` is required: Pallas puts it on the custom call (`kernel_name`)
    and, as a `jax.named_scope`, into the op's scope path, which is how a
    device trace tells one kernel from another (docs/OBSERVABILITY.md).
    """
    from jax.experimental import pallas as pl

    inner = pl.pallas_call(*args, name=name, **kwargs)

    def wrapped(*operands):
        with x64_off():
            return inner(*operands)

    return wrapped


def blank(shape, dtype):
    """A buffer nobody has written: whatever the allocation held on the TPU
    (`jax.lax.empty`, an `AllocateBuffer` there), zeros elsewhere. The ONE
    maker of every packed buffer's guard rows and of every output a kernel
    writes only in part: a test that fills it with NaN shows who reads a
    row nobody computed."""
    import jax

    return jax.lax.empty(tuple(shape), dtype)


class Packed:
    """Rows on their way to a kernel that takes the packed buffer whole:
    `rows [n * slots, ...]`, the kernel's own dtype and columns already
    (its `prepare`), and the `spare` rows its buffer holds past the last
    slot's (what a DMA may run over, the rest of a row tile). `place` makes
    the buffer."""

    def __init__(self, rows, spare: int):
        self.rows, self.spare = rows, spare


def place(outs, slots: int, of: int):
    """The packed buffers of `of` token slots that hold `outs`, a pytree of
    the rows of the first `slots` slots (arrays, or `Packed` with spare
    rows): each at the top of a `blank`, so the rows after them hold
    whatever. A leaf that already fills its buffer is returned as it is."""
    import jax
    from jax.experimental.layout import Layout, with_layout_constraint

    def one(out):
        rows, spare = (out.rows, out.spare) if isinstance(out, Packed) \
            else (out, 0)
        total = rows.shape[0] // slots * of + spare
        if total == rows.shape[0]:
            return rows
        # the rows in the buffer's own (row-major) layout BEFORE they are
        # placed: left to itself the TPU compiler gives the buffer the
        # layout of what made the rows and re-lays the whole of it after
        rows = with_layout_constraint(
            rows, Layout(major_to_minor=tuple(range(rows.ndim))))
        return jax.lax.dynamic_update_slice_in_dim(
            blank((total,) + rows.shape[1:], rows.dtype), rows, 0, 0)

    return jax.tree.map(one, outs, is_leaf=lambda o: isinstance(o, Packed))


def float_dtype_ok(dtype) -> bool:
    """The float dtypes every gate here admits. float16 is out: Mosaic
    for the v5e refuses f16 vectors in each of these kernels ("Invalid
    vector type for load ... vector<8x128x2xf16>"), so f16 callers take
    the XLA composites."""
    import numpy as np

    return np.dtype(dtype).name in ("float32", "bfloat16")


def row_block(rows: int, row_bytes: int) -> int:
    """Row block for a kernel that holds whole rows of `row_bytes` in
    VMEM: the largest power of two dividing `rows` that keeps one block
    near 1 MiB. Each operand is double-buffered and the f32 temporaries
    sit beside them in the 16 MiB scoped limit — a fixed 256-row block
    overflows it for f32 rows of 4096."""
    cap = max(8, (1 << 20) // row_bytes)
    return pick_block(rows, 1 << (cap.bit_length() - 1)) or rows


def pick_block(n: int, preferred: int = 128) -> int:
    """Largest power-of-two block <= preferred that divides n (0 if none >= 8)."""
    b = preferred
    while b >= 8:
        if n % b == 0:
            return b
        b //= 2
    return n if n < 8 else 0
