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
compute-bound at `T` rows (`deepseek_v3_runner`, `cohere2_moe_runner`,
`glm_moe_dsa_runner`) wrap those regions, and not the kernels between them,
in `rowwise`: ONE executable, every kernel in it once, the width chosen on
the device from the step's own `q_lens`. What those three stacks work out
of a step's live rows before their first layer (`prologue`) and count
after their last (`moe_counters`) is here too, once.

A packed buffer that travels between a segment and a kernel is made blank,
once a use, at the shape the kernel takes, and a round writes into it the
rows it computed (`ops/pallas/_support.place`). A guard row of a packed
buffer (a slot at or past `sum(q_lens)`, and the spare rows a kernel's DMA
may run over) holds whatever; every reader of a packed buffer reads live
rows only (docs/SERVING.md lists them).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..models.deepseek_v3 import whole
from ..ops.pallas import _support

__all__ = ["rowwise", "Step", "prologue", "moe_counters"]


def rowwise(n_live, narrow: Optional[int], t: int) -> Callable:
    """`wrap(fn) -> fn'` for a step over a packed buffer of `t` token slots
    with `n_live` live rows (traced int32 scalar, `sum(q_lens)`), all of
    them first in the buffer, and the static `narrow` (the lane count).

    `fn(*rows) -> (row_outs, others)`: every leaf of `rows` and `row_outs`
    has a whole number of rows a token slot as its leading dimension (`w`,
    or `w * k` for an expert layer's assignments, where `fn` is given `w`
    slots) and slot i of an output depends on slot i of the inputs alone;
    `others` (an expert layer's `tokens_per_expert [E]`, or None) have no
    row dimension and count live rows only. A leaf of `row_outs` bound for
    a kernel is a `Packed` (`ops/pallas/_support.py`): its rows, prepared,
    and the spare rows the kernel's buffer has.

    `fn'` takes the packed buffers (`t` slots and whatever spare rows a
    kernel left on them) and returns packed buffers. It is `lax.cond(n_live
    <= narrow, on_prefix, on_all)`: `on_prefix` runs `fn` on the first
    `narrow` slots of every row argument and PLACES what it made at the top
    of a blank buffer (`_support.place`), so of a round of decode lanes
    only the lanes' rows are computed, written or read. A guard row (a slot
    at or past `n_live`, and a spare row) holds WHATEVER: zeros off the
    TPU, what the allocation held on it, what `fn` makes of such a row
    where it was given one. Every reader of a packed buffer reads live rows
    only. The prefix is cut before the `cond` and handed in beside the
    whole buffers: cut inside, the TPU compiler re-laid a whole buffer out
    before slicing it. `on_all` is `models/deepseek_v3.whole(t)(fn)`: `fn`
    over every slot. Where `t <= narrow` (or `narrow` is None) `fn'` is
    `on_all`: no `cond`."""
    if narrow is None or t <= narrow:
        return whole(t)

    def cut(a):
        return a[:a.shape[0] // t * narrow]

    def wrap(fn):
        def on_prefix(head, rows):
            outs, others = fn(*head)
            return _support.place(outs, narrow, t), others

        on_all = whole(t)(fn)
        return lambda *rows: jax.lax.cond(
            n_live <= narrow, on_prefix, lambda head, rows: on_all(*rows),
            jax.tree.map(cut, rows), rows)
    return wrap


class Step(NamedTuple):
    """What a stack knows of a step's live rows before its first layer
    (`prologue`)."""
    live: jax.Array         # [T] bool: no guard slot
    n_live: jax.Array       # [] int32, `sum(q_lens)`
    narrow: Optional[int]   # the lane count where the switch is asked for
    rowwise: Callable       # `rowwise(n_live, narrow, t)`


def prologue(q_lens, tok_pos, narrow: bool) -> Step:
    """The live rows of a step whose `ragged_metadata` gave every packed
    slot the position `tok_pos` [T] (-1: a guard slot), as the three expert
    engines' stacks take them in: which slots are live and how many, and the
    live-prefix switch of the layers' row-wise segments (`narrow`: the
    engine asks for one, at its lane count)."""
    live = tok_pos >= 0
    lanes = q_lens.shape[0] if narrow else None
    n_live = jnp.sum(q_lens.astype(jnp.int32))
    return Step(live, n_live, lanes,
                rowwise(n_live, lanes, tok_pos.shape[0]))


def moe_counters(counters, sizes, step: Step, held=None) -> dict:
    """The expert engines' donated counters after a step: `sizes`, a layer's
    `tokens_per_expert [E]` each, stacked `[L, E]` and added to `tokens`;
    `touched [L]` the experts of the `held` range `(first, count)` (None:
    all) that got a token; `steps`; `narrow_steps`, the steps whose live
    rows fit the switch's prefix. `inference/step_engine.expert_load` reads
    them."""
    sizes = jnp.stack(sizes)                                     # [L, E]
    # (the sum before the cut: the order the steps' lowered text has)
    tokens = counters["tokens"] + sizes
    mine = sizes if held is None else sizes[:, held[0]:held[0] + held[1]]
    return {
        "tokens": tokens,
        "touched": counters["touched"] + jnp.sum(mine > 0, axis=1,
                                                 dtype=jnp.int32),
        "steps": counters["steps"] + 1,
        "narrow_steps": counters["narrow_steps"] + (
            (step.n_live <= step.narrow).astype(jnp.int32)
            if step.narrow else 0),
    }
