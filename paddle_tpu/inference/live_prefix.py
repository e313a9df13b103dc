"""A row-wise region of the fixed-shape serving step, run over the live prefix
of the packed buffer.

`ragged_metadata` packs the lanes' tokens contiguously from slot 0, so the
live rows of a step are exactly slots `0 .. sum(q_lens) - 1` and every slot
after them is a guard. A round without a prefill chunk has at most `lanes`
live rows of `T = lanes + prefill_chunk_tokens`. Two things in a decoder
layer already cost what is live whatever `T` is: `attend` (the pool's write
and the paged-attention kernel follow live pages) and an expert layer's
grouped matmuls (they follow the experts touched). Everything else maps a row
to a row and costs its rows. So the engines whose row-wise regions are
compute-bound at `T` rows (`deepseek_v3_runner`, `cohere2_moe_runner`) wrap
those regions, and not the kernels between them, in `rowwise`: ONE
executable, every kernel in it once, the width chosen on the device from the
step's own `q_lens`.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..models.deepseek_v3 import whole

__all__ = ["rowwise"]


def rowwise(n_live, narrow: Optional[int], t: int) -> Callable:
    """`wrap(fn) -> fn'` for a step over a packed buffer of `t` token slots
    with `n_live` live rows (traced int32 scalar, `sum(q_lens)`), all of
    them first in the buffer, and the static `narrow` (the lane count).

    `fn(*rows) -> (row_outs, others)`: every leaf of `rows` and `row_outs`
    has a whole number of rows a token slot as its leading dimension (`t`,
    or `t * k` for an expert layer's assignments) and slot i of an output
    depends on slot i of the inputs alone; `others` (an expert layer's
    `tokens_per_expert [E]`, or None) have no row dimension and count live
    rows only. `fn'` is `lax.cond(n_live <= narrow, on_prefix, fn)`:
    `on_prefix` runs `fn` on the first `narrow` slots of every row argument
    and zero-pads `row_outs` back to `t` slots, so a guard row reads exact
    zeros where `fn` leaves whatever a guard row computes; nothing reads
    either. Where `t <= narrow` (or `narrow` is None) `fn'` is `fn`: no
    `cond`."""
    if narrow is None or t <= narrow:
        return whole

    def wrap(fn):
        def on_prefix(*rows):
            # cut first and hold the cut: fused into its consumer, the
            # TPU compiler re-laid the whole buffer out before slicing it
            head = jax.lax.optimization_barrier(jax.tree.map(
                lambda a: a[:a.shape[0] // t * narrow], rows))
            outs, others = fn(*head)
            return jax.tree.map(
                lambda a: jnp.pad(a, ((0, a.shape[0] // narrow
                                       * (t - narrow)),)
                                  + ((0, 0),) * (a.ndim - 1)),
                outs), others

        return lambda *rows: jax.lax.cond(n_live <= narrow, on_prefix, fn,
                                          *rows)
    return wrap
