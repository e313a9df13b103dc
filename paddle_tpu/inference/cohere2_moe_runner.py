"""Serving engine for the Cohere2-MoE architecture (`models/cohere2_moe.py`):
an `EngineCore` whose layers are of two kinds that keep different amounts of
context, so it has TWO paged pools and two block tables a sequence.

- The groups come from the model: `full_attention` layers keep every token
  (group 0, `"full"`), `sliding_attention` layers only the last
  `sliding_window` (group 1, `"window"`, whose blocks the manager gives back
  behind the window: `inference/cache.py`). A model of one kind has one
  group. Nothing here is an option.
- The pools are ONE donated tuple `(k_full, v_full, k_window, v_window)`,
  each `[layers of the kind, blocks of the group, kv heads, block, head]`,
  written in place and indexed `[layer_in_group, block]`; the layers are
  unrolled (as the DeepSeek-V3 engine's). The scheduler hands every engine
  one 2-D table; this one reads its groups' columns of it,
  `tables[:, g * W:(g + 1) * W]` (`BlockCacheManager.block_table_array`).
- One attention kernel for both kinds, `paged_attention_ragged`, the window
  one more prefetched scalar (0 for a full layer); the scopes
  `llama.attn_window` / `llama.attn_full` tell its calls apart in a trace.
- The weights are the model's own pytree, by reference; the expert layer
  holds `config.held_experts` of the router's experts (`[held, in, out]`).
- The `EngineCore` surface and the three programs are the shell's
  (`inference/step_engine.StepEngine`); this file holds the stack, the
  head and the two pools' layout.
- Expert load is counted inside the step, on the device, in donated
  counters; `expert_load()` reads them.

The engine transforms (`quantize_engine`, `shard_engine`, `attach_adapters`)
look for a Llama or an MLP parameter layout and refuse this engine by its
name; KV migration is refused here, by family, and the scheduler refuses the
radix prefix cache and speculative decoding over a windowed group.
"""
from __future__ import annotations

import functools
import time
from typing import Dict

import jax
import jax.numpy as jnp

from ..models import cohere2_moe as c2
from ..ops.pallas import paged_attention as pk
from . import live_prefix, step_engine
from .cache import BlockCacheManager

__all__ = ["Cohere2MoeInferenceEngine"]

FAMILY = "cohere2_moe"
# a group a layer kind, in the order of the pools and of the table's columns
GROUPS = ((c2.FULL, "full"), (c2.SLIDING, "window"))


def _groups(cfg: c2.Cohere2MoeConfig):
    """`(kind, name, layers of the kind)` for the kinds the model has."""
    return tuple((kind, name, cfg.layers_of(kind)) for kind, name in GROUPS
                 if cfg.layers_of(kind))


def _ragged_stack(params, pools, counters, tokens, q_lens, kv_lens, tables,
                  *, cfg: c2.Cohere2MoeConfig, narrow: bool = False):
    """Packed tokens `[T]` + per-lane `(q_len, kv_len)` through the decoder:
    `(logits [T, V] float32, pools, counters)`. `narrow`: a step whose live
    rows number at most its lanes runs the layers' row-wise segments over
    that prefix of the packed buffer (`live_prefix.rowwise`; the choice is
    made on the device, from `q_lens`)."""
    t = tokens.shape[0]
    kv_lens = kv_lens.astype(jnp.int32)
    tables = tables.astype(jnp.int32)
    groups = _groups(cfg)
    width = tables.shape[1] // len(groups)
    tok_lane, tok_pos = pk.ragged_metadata(q_lens, kv_lens, t)
    step = live_prefix.prologue(q_lens, tok_pos, narrow)
    with jax.named_scope("llama.rope"):
        pos = jnp.maximum(tok_pos, 0)
    cos, sin, x = step_engine.token_rows(params, tokens, pos)
    pools = list(pools)
    where = {}        # layer -> (its group, its index in the group's pool)
    for g, (_kind, _name, layers) in enumerate(groups):
        where.update({layer: (g, n) for n, layer in enumerate(layers)})

    # the kernel takes the packed buffer as the first segment leaves it
    # (`attend.pack`: `pk.ragged_prepare`, placed by the segment's
    # `rowwise`) and hands its own buffer to the second (`attend.unpack`)
    nh, kvh, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    packed = all(pk.ragged_supported(
        (t, nh, d), x.dtype, pools[2 * g].shape, pools[2 * g].dtype, width)
        for g in range(len(groups)))

    def attend_layer(i, kind):
        g, n = where[i]
        table = tables[:, g * width:(g + 1) * width]
        window = cfg.sliding_window if kind == c2.SLIDING else 0
        scope = "llama.attn_window" if window else "llama.attn_full"

        def attend(q, k, v):
            with jax.named_scope("llama.kv_write"):
                pools[2 * g], pools[2 * g + 1] = pk.write_kv_to_cache_ragged(
                    k, v, pools[2 * g], pools[2 * g + 1], table, tok_lane,
                    tok_pos, layer=n)
            with jax.named_scope(scope):
                kc, vc = pools[2 * g], pools[2 * g + 1]
                if packed:
                    return pk.paged_attention_ragged_packed(
                        q, kc, vc, table, kv_lens, tok_lane, tok_pos, layer=n,
                        window=window, mxu_bf16=x.dtype == jnp.bfloat16)
                return pk.paged_attention_ragged_ref(
                    q, kc, vc, table, kv_lens, tok_lane, tok_pos, layer=n,
                    window=window)
        if packed:
            attend.pack = functools.partial(pk.ragged_prepare, kv_heads=kvh)
            attend.unpack = functools.partial(pk.ragged_finish, heads=nh,
                                              dtype=x.dtype)
        return attend

    sizes = []
    for i, kind in enumerate(cfg.layer_types):
        x, n = c2.decoder_layer(x, c2.layer_params(params, i), cfg, kind, cos,
                                sin, attend_layer(i, kind), step.live,
                                step.rowwise)
        sizes.append(n)
    return x, tuple(pools), live_prefix.moe_counters(counters, sizes, step,
                                                     cfg.held)


def _head(state, x, lane, *, cfg):
    """The `head` of `ops/sampling.with_tail`: the final norm and the tied
    output matmul over the rows it is given; `state[0]` is the params."""
    return c2.head(x, state[0], cfg)


class Cohere2MoeInferenceEngine(step_engine.StepEngine):
    """`EngineCore` over `Cohere2MoeForCausalLM` with a pool a layer kind.
    Serves in the dtype the model's weights have.

    `num_blocks` sizes the pool of the layers that keep every token;
    `window_blocks` that of the sliding-window layers beside them (a lane
    holds at most `(window + tokens a step - 2) // block_size + 2` of its
    blocks at once; by default every lane's whole table fits, plus the
    guard block)."""

    FAMILY = FAMILY
    DONATED = ("pools", "counters")
    NO_MIGRATION = ("two pools of different geometry (a window's and a "
                    "context's) have no migration payload yet")

    def __init__(self, model: c2.Cohere2MoeForCausalLM,
                 max_batch_size: int = 8, num_blocks: int = 256,
                 block_size: int = 16, max_blocks_per_seq: int = 16,
                 window_blocks: int = None):
        began = time.time()     # `engine.build_s`: this line to the last
        cfg = model.config
        self.config = cfg
        self.block_size = block_size
        self.max_batch_size = max_batch_size
        groups = _groups(cfg)
        self.group_names = tuple(name for _k, name, _l in groups)
        kvh, d = cfg.num_key_value_heads, cfg.head_dim
        cos, sin = c2.rope_tables(cfg, max_blocks_per_seq * block_size)
        # the model's own arrays, by reference, beside the rope tables
        self.params: Dict[str, jax.Array] = dict(
            model.weight_tree(), rope_cos=cos, rope_sin=sin)
        cdtype = self.params["model.embed_tokens.weight"].dtype
        # the first group keeps every block (a model of sliding layers
        # alone has one group, which the kernel's window still bounds);
        # the window group beside a full one releases behind its window
        if window_blocks is None:
            window_blocks = max_batch_size * max_blocks_per_seq + 1
        sizes = [(groups[0][1], num_blocks, None)] + [
            (name, window_blocks, cfg.sliding_window)
            for _kind, name, _layers in groups[1:]]
        self.manager = BlockCacheManager(
            num_blocks, block_size, max_blocks_per_seq, name=sizes[0][0],
            further_groups=sizes[1:])
        itemsize = jnp.dtype(cdtype).itemsize
        pools = []
        self._group_bytes_per_token = {}
        for g, ((_kind, name, layers), (_n, nb, _w)) in enumerate(
                zip(groups, sizes)):
            shape = (len(layers), nb, kvh, block_size, d)
            pools += [jnp.zeros(shape, cdtype), jnp.zeros(shape, cdtype)]
            per_token = 2 * len(layers) * kvh * d * itemsize
            self._group_bytes_per_token[name] = per_token
            self.manager.set_kv_geometry(per_token * block_size, 16, group=g)
        self.pools = tuple(pools)
        L, e = cfg.num_hidden_layers, cfg.num_experts
        self.counters = {"tokens": jnp.zeros((L, e), jnp.int32),
                         "touched": jnp.zeros((L,), jnp.int32),
                         "steps": jnp.zeros((), jnp.int32),
                         "narrow_steps": jnp.zeros((), jnp.int32)}

        self._build_programs(
            functools.partial(_ragged_stack, cfg=cfg, narrow=True),
            functools.partial(_head, cfg=cfg),
            window=functools.partial(_ragged_stack, cfg=cfg), began=began)

    # ---- hooks the scheduler and the cache manager look for ----
    def kv_bytes_per_token(self, group: str = None) -> float:
        """HBM bytes one cached token costs in `group`'s pool (K + V over
        the group's layers). Without a group: what a token costs for as
        long as its sequence lives, the first group's; a windowed group's
        bytes are a token's only while it lies inside the window, and are
        reported under the group's own name (`quant_info`)."""
        return float(self._group_bytes_per_token[
            self.group_names[0] if group is None else group])

    def quant_info(self) -> dict:
        """What `serving.quant.*` and `serving.kv_bytes_per_token[.<group>]`
        publish."""
        return dict(super().quant_info(), kv_bytes_per_token_by_group=dict(
            self._group_bytes_per_token))

    # ---- expert load ----
    def expert_load(self) -> dict:
        """The step's device-side counters, fetched now
        (`step_engine.expert_load`, over the held experts: `tokens` counts
        the ROUTER's experts, held and absent alike)."""
        return step_engine.expert_load(self.counters, self.config.held)
