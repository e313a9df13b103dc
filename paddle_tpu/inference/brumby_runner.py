"""Serving engine for the Brumby architecture (`models/brumby.py`): an
`EngineCore` with no KV cache at all. What a sequence leaves on the device
is ONE recurrent state a layer and KV head, `slots` of them, and the cache
manager hands out slots (`BlockCacheManager.state_group`).

An engine of its own and not the Llama engine given another `attend`: that
one stacks its weights on a layer axis (the build transient that keeps
Mistral at 12 layers) and scans them beside a `(k, v)` pool and a block
table; here the weights are the model's own pytree, by reference, the layers
unrolled as the two MoE engines', and nothing is paged.

- The state is ONE donated tuple `(S [L, slots + 1, kv heads, offsets, d, d]
  f32, z [L, slots + 1, kv heads, offsets, d] f32, length [slots + 1] i32,
  resets [] i32)`, written in place by the two kernels of
  `ops/pallas/power_retention.py` (their `jnp` twins where
  `retention_supported` says no). `block_tables` is `[B, 1]`: the slot.
- A lane whose `kv_len - q_len` is 0 STARTS FROM A ZERO STATE inside the
  step (no host call, no second program; counted in `resets`). A lane whose
  `kv_len - q_len` is not what its slot holds (`length`) would apply a token
  twice or skip one: its state is left untouched and its rows are made NaN,
  so the step's own screen flags the lane (`ops/sampling.step_tail`) and the
  scheduler fails the request. A state cannot be trimmed, so nothing here
  replays a token.
- The `EngineCore` surface and the programs are the shell's
  (`inference/step_engine.StepEngine`); this file holds the stack, the
  head and the state's layout. `verify_step` raises: a verify window's
  rollback needs a snapshot of the state.

The engine transforms (`quantize_engine`, `shard_engine`, `attach_adapters`)
look for a Llama or an MLP parameter layout and refuse this engine by its
name; KV migration is refused here, and the scheduler refuses the radix
prefix cache and speculative decoding over a state group.
"""
from __future__ import annotations

import functools
import time
from typing import Dict

import jax
import jax.numpy as jnp

from ..framework import monitor
from ..models import brumby as bm
from ..ops.pallas import power_retention as pr
from ..ops.pallas.paged_attention import ragged_metadata
from . import step_engine
from .cache import BlockCacheManager

__all__ = ["BrumbyInferenceEngine"]

FAMILY = "brumby"


def _retain(state, layer, cfg, slot, rows, update, chunk, fresh, tok_lane):
    """`retain` of layer `layer` over the step's packed rows: the decode
    lanes (`update [B]`) through the one-token kernel on their own rows
    (`rows [B]`), the chunk lanes through the chunked one, each from and to
    its slot of `state`, a list `[S, z]` that is replaced."""
    ok = pr.retention_supported(cfg.head_dim, jnp.float32)
    kernels = (pr.power_retention_update, pr.power_retention_chunk) if ok \
        else (pr.power_retention_update_ref, pr.power_retention_chunk_ref)
    common = dict(layer=layer, slot=slot, fresh=fresh, eps=cfg.retention_eps)
    decode_row = update[jnp.maximum(tok_lane, 0)] & (tok_lane >= 0)

    def retain(q, k, v, a):
        with jax.named_scope("llama.retention_update"):
            y1, state[0], state[1] = kernels[0](
                q[rows], k[rows], v[rows], a[rows], *state, live=update,
                **common)
        with jax.named_scope("llama.retention_chunk"):
            y, state[0], state[1] = kernels[1](
                q, k, v, a, *state, live=chunk, tok_lane=tok_lane, **common)
        return jnp.where(decode_row[:, None, None, None],
                         y1[jnp.maximum(tok_lane, 0)], y)
    return retain


def _ragged_stack(params, state, tokens, q_lens, kv_lens, tables, *, cfg):
    """Packed tokens `[T]` + per-lane `(q_len, kv_len)` through the decoder:
    `(hidden [T, H] before the final norm, state)`, the `stack` of
    `ops/sampling.with_tail`."""
    S, z, length, resets = state
    t = tokens.shape[0]
    q_lens, kv_lens = q_lens.astype(jnp.int32), kv_lens.astype(jnp.int32)
    slot = tables[:, 0].astype(jnp.int32)
    tok_lane, tok_pos = ragged_metadata(q_lens, kv_lens, t)
    tok_lane = jnp.where(tok_pos >= 0, tok_lane, -1)
    start = kv_lens - q_lens
    live = q_lens > 0
    fresh = live & (start == 0)
    sound = live & (fresh | (start == length[slot]))
    with jax.named_scope("llama.rope"):
        pos = jnp.maximum(tok_pos, 0)
    cos, sin, x = step_engine.token_rows(params, tokens, pos)
    held = [S, z]
    rows = jnp.maximum(jnp.cumsum(q_lens) - 1, 0)      # a lane's last row
    for i in range(cfg.num_hidden_layers):
        x = bm.decoder_layer(
            x, bm.layer_params(params, i), cfg, cos, sin,
            _retain(held, i, cfg, slot, rows, sound & (q_lens == 1),
                    sound & (q_lens > 1), fresh, tok_lane))
    # a lane that would replay or skip a token: NaN rows, for the screen
    amiss = (live & ~sound)[jnp.maximum(tok_lane, 0)] & (tok_lane >= 0)
    x = jnp.where(amiss[:, None], jnp.nan, x)
    at = jnp.where(sound, slot, length.shape[0])       # others are dropped
    state = (held[0], held[1], length.at[at].set(kv_lens, mode="drop"),
             resets + jnp.sum(fresh & sound, dtype=jnp.int32))
    return x, state


def _head(state, x, lane, *, cfg):
    """The `head` of `ops/sampling.with_tail`: the final norm and the output
    matmul over the rows it is given; `state[0]` is the params."""
    return bm.head(x, state[0], cfg)


class BrumbyInferenceEngine(step_engine.StepEngine):
    """`EngineCore` over `BrumbyForCausalLM` with one state slot a sequence.
    Serves in the dtype the model's weights have; the state is float32.

    `slots`: sequences that can be resident at once (one more is allocated
    for a scheduler's guard); by default one a lane. `context_tokens`: the
    longest a sequence may grow, the rotary table's length."""

    FAMILY = FAMILY
    DONATED = ("state",)
    NO_VERIFY = ("verify_step over a state group is not implemented: "
                 "rejecting a draft would have to roll the state back, and a "
                 "recurrent state has no snapshot yet")
    NO_MIGRATION = "a state slot has no migration payload yet"

    def __init__(self, model: bm.BrumbyForCausalLM, max_batch_size: int = 8,
                 slots: int = None, context_tokens: int = None):
        began = time.time()     # `engine.build_s`: this line to the last
        cfg = model.config
        self.config = cfg
        self.max_batch_size = max_batch_size
        slots = max_batch_size if slots is None else slots
        context_tokens = context_tokens or cfg.max_position_embeddings
        if context_tokens > cfg.max_position_embeddings:
            raise ValueError(
                f"{FAMILY}: {context_tokens} context tokens are more than "
                f"the {cfg.max_position_embeddings} positions of the model")
        cos, sin = bm.rope_tables(cfg)
        # the model's own arrays, by reference, beside the rope tables
        self.params: Dict[str, jax.Array] = dict(
            model.weight_tree(), rope_cos=cos[:context_tokens],
            rope_sin=sin[:context_tokens])
        self.manager = BlockCacheManager.state_group(slots, context_tokens)
        # what the engine transforms ask every engine for: a state group's
        # one block a sequence spans every position
        self.block_size = self.manager.block_size
        s_shape, z_shape = bm.state_shapes(cfg, slots + 1)
        self.state = (jnp.zeros(s_shape, jnp.float32),
                      jnp.zeros(z_shape, jnp.float32),
                      jnp.zeros((slots + 1,), jnp.int32),
                      jnp.zeros((), jnp.int32))
        self.manager.set_kv_geometry(self.state_bytes_per_seq(), 32)
        self._build_programs(functools.partial(_ragged_stack, cfg=cfg),
                             functools.partial(_head, cfg=cfg), began=began)

    # ---- hooks the scheduler and the cache manager look for ----
    def kv_bytes_per_token(self) -> float:
        """No byte of this engine's memory grows with a token."""
        return 0.0

    def state_bytes_per_seq(self) -> int:
        """HBM bytes one resident sequence holds, whatever its length: `S`
        and `z` over every layer and KV head, float32."""
        S, z = self.state[:2]
        return int((S[0, 0].size + z[0, 0].size) * S.shape[0] * 4)

    def quant_info(self) -> dict:
        """What `serving.quant.*`, `serving.kv_bytes_per_token` and
        `serving.state.bytes_per_seq` publish."""
        return dict(super().quant_info(),
                    state_bytes_per_seq=self.state_bytes_per_seq())

    # ---- the state's counter ----
    def state_resets(self) -> int:
        """Lanes started from a zero state since the engine was built: the
        counter the step keeps on the device, fetched now; publishes
        `serving.state.resets`."""
        n = int(jax.device_get(self.state[3]))
        monitor.set_value("serving.state.resets", n)
        return n
