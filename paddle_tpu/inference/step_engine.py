"""The engine shell: what every serving engine class is, written once.

An engine class is STATE + a STACK + a HEAD. Its constructor lays out the
state (the params; pools, counters or a recurrent state, and the cache
manager that hands their blocks or slots out), names the attributes a step
replaces (`DONATED`), and hands `_build_programs` the two halves of its
model: `stack` `(*state, tokens, q_lens, kv_lens, tables) -> (hidden [T, H],
*donated)` and `head` `(state, rows [N, H], lane [N]) -> logits [N, V]`.
`StepEngine` makes the `EngineCore` surface (`serving/engine.py`) of them:

- the three programs, each one `jax.jit` with the donated state's positions
  donated: `_ragged` (`ops/sampling.with_tail`: a round's ONE program, ending
  in the NaN screen, the head over the sampled rows and the sampler),
  `_logits` (`all_rows`: the head over every row, compiled when
  `ragged_step` first calls it) and `_verify` (`verify_windows`: every lane
  a window of `S` tokens);
- `sampled_step`, `ragged_step`, `verify_step`, `generate`, over `_run`,
  which feeds a program the state and keeps what it returns of it;
- `cost_card_args`, the default `quant_info`, and the refusals a family
  states as one sentence (`NO_VERIFY`, `NO_MIGRATION`).

So a new architecture costs a model module, a runner file that holds a
stack, a head and a pool layout, and no copy of this file (docs/SERVING.md
"Adding an architecture"); a change to the seam between the scheduler and
the engines is an edit here and in `ops/sampling.py`.

Beside the class: `BlockCopy` (the cache manager's COW hook for a family
that pages by block and refuses migration), and the two ends of an unrolled
stack that are no family's own: `token_rows` here, `live_prefix.prologue`
and `live_prefix.moe_counters` beside `rowwise`, and `expert_load`, the
host's reading of those counters.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import monitor
from ..observability import compile_trace
from ..ops import sampling
from . import kv_migrate
from .generate import generate

__all__ = ["StepEngine", "BlockCopy", "token_rows", "expert_load"]


class StepEngine:
    """The `EngineCore` surface over `self.params`, the attributes `DONATED`
    names and the programs `_build_programs` compiled."""

    FAMILY: str = None          # names the family in what it refuses
    # the attributes a step program takes after the params and replaces
    # with what it returns, in the order it takes and returns them
    DONATED = ("pools",)
    # every leading argument of a step program where more than the params
    # and the donated state lead (`LoRAEngine`: adapters and lane slots)
    LEADING: tuple = None
    # why the family has no `verify_step` / no KV migration, one sentence
    # each (an engine that migrates has `kv_migrate.PagedPools` ahead of
    # this class in its bases)
    NO_VERIFY: str = None
    NO_MIGRATION: str = None
    # `cost_card_args`' phases -> the program's attribute; the serving
    # scheduler's "decode" phase is the ragged step, its only decode program
    PHASES = {"decode": "_ragged", "ragged": "_ragged", "verify": "_verify"}

    def _build_programs(self, stack, head, window=None, began=None):
        """The engine's programs from its `stack` and `head`
        (`ops/sampling.with_tail`). `window`: the stack of the verify
        program where it is not `stack` (a window is never a live prefix:
        every row of it is live). `began`: the `time.time()` of the
        constructor's first line, this call being its last: the
        `engine.build` stamp."""
        self._lead = lead = self.LEADING or ("params", *self.DONATED)
        donate = tuple(lead.index(name) for name in self.DONATED)
        # a wrapper's inner function brings the XLA module's name
        # (`jit__ragged_fn`, `jit__logits_fn`, `jit__verify_fn`), which is
        # how a profile's "XLA Modules" line tells the steps
        self._ragged = jax.jit(sampling.with_tail(stack, head),
                               donate_argnums=donate)
        self._logits = jax.jit(sampling.all_rows(stack, head),
                               donate_argnums=donate)
        if self.NO_VERIFY is None:
            self._verify = jax.jit(
                sampling.verify_windows(window or stack, head),
                donate_argnums=donate)
        self.last_sampled = None    # the last step's `sampled`, on device
        if began is not None:
            compile_trace.stamp("engine.build", began)

    # ---- the EngineCore dispatch surface ----
    def sampled_step(self, tokens: np.ndarray, lanes: np.ndarray,
                     block_tables: np.ndarray, temperature: np.ndarray):
        """ONE fixed-shape step over a packed ragged batch, sampled — the
        serving scheduler's only decode-path program (chunked prefill +
        decode lanes fused; see docs/SERVING.md "Ragged batching").

        tokens [T] int32: packed lane-major query tokens; lane i owns
        slots [sum(q_lens[:i]), sum(q_lens[:i]) + q_lens[i]), its token j
        landing at position `kv_lens[i] - q_lens[i] + j` (kv_lens counts
        the cache INCLUDING this step's tokens; q_lens[i] == 0 marks an
        empty lane). `lanes` [B, 6] int32 carries q_lens, kv_lens and
        each lane's last packed row, top_k, seed and draw index
        (`ops/sampling.LANE_COLS`); `temperature` [B] float32;
        `block_tables` [B, W]: every group's table of a lane, side by side
        (a state group's: `[B, 1]`, the lane's slot). Returns `sampled`
        [2, B] int32, left on the device: each lane's token and whether its
        band is all finite (`ops/sampling.step_tail`). The head runs over
        the `B` sampled rows alone; rows at guard slots past sum(q_lens)
        are meaningless and ignored (they write nothing, reach no expert,
        and their attention output is never read). Shape-stable in
        everything but T, which the scheduler fixes at `max_batch_size +
        prefill_chunk_tokens` — one compiled executable regardless of
        batch composition or prompt length."""
        self.last_sampled = self._run(
            self._ragged, *sampling.call_arrays(
                tokens, lanes, block_tables, temperature, self.last_sampled))
        return self.last_sampled

    def _run(self, fn, *arrays):
        """One of the step programs over this engine's state, which it
        replaces; what the program returns ahead of it."""
        out, *state = fn(*(getattr(self, name) for name in self._lead),
                         *arrays)
        for name, value in zip(self.DONATED, state):
            setattr(self, name, value)
        return out

    ragged_step = sampling.ragged_step

    def verify_step(self, tokens: np.ndarray, context_lens: np.ndarray,
                    block_tables: np.ndarray):
        """Batched multi-token verify pass (speculative decoding).

        tokens [B, S] int32 — per row, the pending last committed token
        followed by S-1 draft tokens; `context_lens` [B] counts the cache
        INCLUDING all S of them, so token i is written at position
        `context_lens - S + i` and attends causally up to itself (same
        fixed shape every step: zero recompiles once traced). Returns
        logits [B, S, V]: row i is the distribution for the token AFTER
        tokens[:, i] — rows 0..S-2 verify the drafts, row S-1 samples the
        bonus token when every draft is accepted. The args go to the jit
        as exact-dtype numpy (see `ops/sampling.call_arrays`)."""
        if self.NO_VERIFY is not None:
            raise NotImplementedError(f"{self.FAMILY}: {self.NO_VERIFY}")
        return self._run(self._verify, np.asarray(tokens, np.int32),
                         np.asarray(context_lens, np.int32),
                         np.asarray(block_tables, np.int32))

    generate = generate

    # ---- hooks the scheduler, the metrics and the router look for ----
    def cost_card_args(self, phase: str):
        """Observability hook (`observability.costs.ensure_engine_card`):
        the jitted executable behind `phase` (`PHASES`) plus the leading
        arguments the scheduler never sees (the params, the donated
        state). The scheduler appends its own call arrays and lowers the
        pair — never executes it — for `cost_analysis()` /
        `memory_analysis()`: compiler-reported FLOPs per dispatch (per
        chip, for an SPMD program). A phase the engine has no program for
        raises (the caller tombstones)."""
        return getattr(self, self.PHASES[phase]), tuple(
            getattr(self, name) for name in self._lead)

    def quant_info(self) -> dict:
        """What `serving.quant.*` and `serving.kv_bytes_per_token` publish:
        an engine that serves in the dtype of its model's weights."""
        return {"wbits": 16, "kv_bits": 16,
                "kv_bytes_per_token": self.kv_bytes_per_token()}

    def extract_kv_blocks(self, seq_id: int):
        raise kv_migrate.KVMigrationError(
            f"{self.FAMILY}: {self.NO_MIGRATION}")

    def inject_kv_blocks(self, seq_id: int, payload) -> None:
        raise kv_migrate.KVMigrationError(
            f"{self.FAMILY}: {self.NO_MIGRATION}")


class BlockCopy:
    """`copy_kv_block`, the cache manager's COW hook (the scheduler wires it
    when prefix caching is on), for a family that pages by block and refuses
    migration: `kv_migrate.PagedPools`' copy over the family's first donated
    attribute, blocks on axis 1. ONE donated executable, every pool of the
    attribute and every layer in it; `src` / `dst` trace as scalars, so a
    COW never recompiles."""

    _build_block_ops = kv_migrate.PagedPools._build_block_ops

    def copy_kv_block(self, src: int, dst: int) -> None:
        name = self.DONATED[0]
        setattr(self, name, self._copy_block(
            getattr(self, name), np.int32(src), np.int32(dst)))


def token_rows(params, tokens, pos):
    """The rows an unrolled stack starts from: `(cos, sin, x)`, the rotary
    table's rows at each packed token's position `pos` [T] (a guard slot's
    clamped to 0) and the embedding's rows of `tokens` [T]."""
    with jax.named_scope("llama.rope"):
        cos = jnp.take(params["rope_cos"], pos, axis=0)
        sin = jnp.take(params["rope_sin"], pos, axis=0)
    with jax.named_scope("llama.embed"):
        x = jnp.take(params["model.embed_tokens.weight"], tokens, axis=0)
    return cos, sin, x


def expert_load(counters, held=None, first_dense: int = 0) -> dict:
    """The counters an expert engine's step keeps on the device
    (`live_prefix.moe_counters`), fetched now: `tokens [L, E]` routed to
    each of the ROUTER's experts since the engine was built, `touched [L]`
    experts with at least one token summed over steps, `steps`,
    `narrow_steps` (those whose row-wise work ran over the live prefix).
    `held` `(first, count)`: the engine holds that range of the router's
    experts (the load is theirs, `touched` counts them alone, and the range
    comes back under `"held"`); None: all of them. `first_dense`: the
    leading layers that have no experts (their rows are zeros).

    Publishes `serving.moe.expert_tokens` (assignments that fell on an
    expert the engine holds), for a held range
    `serving.moe.held_assignment_share` (their share of all assignments),
    and the gauges `serving.moe.load_max_over_mean` (busiest held expert of
    an expert layer against the mean one) and
    `serving.step.live_prefix_share` (`narrow_steps / steps`)."""
    c = jax.device_get(counters)
    tokens = np.asarray(c["tokens"], np.int64)
    mine = tokens if held is None else tokens[:, held[0]:held[0] + held[1]]
    moe = mine[first_dense:]
    monitor.set_value("serving.moe.expert_tokens", int(moe.sum()))
    if held is not None and tokens.sum():
        monitor.set_gauge("serving.moe.held_assignment_share",
                          round(float(mine.sum() / tokens.sum()), 4))
    if moe.sum():
        monitor.set_gauge("serving.moe.load_max_over_mean",
                          round(float(moe.max() / moe.mean()), 3))
    steps, narrow = int(c["steps"]), int(c["narrow_steps"])
    if steps:
        monitor.set_gauge("serving.step.live_prefix_share",
                          round(narrow / steps, 4))
    load = {"tokens": tokens, "touched": np.asarray(c["touched"], np.int64),
            "steps": steps, "narrow_steps": narrow}
    return load if held is None else dict(load, held=tuple(held))
