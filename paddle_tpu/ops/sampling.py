"""Device-side fused batched token sampling for the serving decode loop.

Replaces the scheduler's per-lane host numpy sampling (`np.argmax` /
softmax + `Generator.choice` per request) with ONE jitted program over the
whole batch: temperature scaling, per-lane top-k filtering, and Gumbel-max
sampling under a counter-based per-request RNG. The TPU analog of the
reference's fused sampling kernels (`phi/kernels/fusion/gpu/
fused_softmax_mask_kernel.cu` + top_k sampling ops): sampling must not
serialize the decode loop on a host round-trip per lane.

Shape discipline matches the serving engines: the program is traced once
per (B, S, V) shape — [B, 1, V] for the normal decode path, [B, K+1, V]
for the speculative verify path — and bumps `serving.sample_retraces` at
trace time so tests can assert the zero-recompile steady state.

Determinism: lane b / slot s draws with key
`fold_in(fold_in(base, seed[b]), draw_idx[b] + s)` where `draw_idx` is the
number of tokens the request has drawn so far — reproducible across runs,
preemptions, and batch-slot churn (the lane index never enters the key).
Greedy lanes (temperature <= 0) take a pure argmax and ignore the RNG.

The plain serving round runs none of this as a program of its own: every
engine compiles its ragged step through `with_tail`, so the NaN screen,
the gather of each lane's last row, the output head over those `B` rows and
the sampler above are the END of the step's one program (`step_tail`), and
what crosses to the host is one `[2, B]` int32 array: no `[T, V]` array is
ever made. `sample_tokens` stays for the speculative verify round, whose
`[B, S, V]` logits (the engine's THIRD program, `verify_windows`) it samples
whole. A caller that wants every packed row's logits (`generate`,
proposers, a fault probe) takes the engine's SECOND program, `all_rows`
over the same stack and head (`ragged_step`). The three wrappers are all an
engine compiles of its model (`inference/step_engine.StepEngine`).

A round's decode tokens need not cross to the host between two rounds:
`with_tail` reads a token `-(b + 1)` as "what lane `b` sampled in this
engine's previous sampled step", out of that step's `sampled`, which every
engine keeps on the device (`last_sampled`) and `call_arrays` sends back
in. The scheduler launches round n+1 on such tokens before it has fetched
round n (docs/SERVING.md "A round in flight").
"""
from __future__ import annotations

import functools

import numpy as np

from ..framework import monitor

__all__ = ["sample_tokens", "step_tail", "with_tail", "all_rows",
           "verify_windows", "pack_lanes", "call_arrays", "step_args",
           "ragged_step", "fed_token", "LANE_COLS"]

# the per-lane int32 block of a sampled step, one column each: with
# `tokens`, `tables` and `temperature` it is everything a round sends
LANE_COLS = ("q_len", "kv_len", "row", "top_k", "seed", "draw_idx")
_Q_LEN, _KV_LEN, _ROW, _TOP_K, _SEED, _DRAW = range(len(LANE_COLS))


def _sample_fn(logits, temperature, top_k, seeds, draw_idx):
    """logits [B,S,V] f32; temperature [B]; top_k [B]; seeds/draw_idx [B]."""
    import jax
    import jax.numpy as jnp

    monitor.inc("serving.sample_retraces")  # trace-time only
    b, s, v = logits.shape
    x0 = logits.astype(jnp.float32)
    greedy = jnp.argmax(x0, axis=-1).astype(jnp.int32)         # [B, S]

    def stochastic(_):
        x = x0 / jnp.maximum(temperature, 1e-6)[:, None, None]
        # per-lane top-k: k-th largest as threshold (k == 0 -> keep all)
        sorted_desc = -jnp.sort(-x, axis=-1)
        k = jnp.where(top_k > 0, jnp.clip(top_k, 1, v), v).astype(jnp.int32)
        kth = jnp.take_along_axis(
            sorted_desc, jnp.broadcast_to((k - 1)[:, None, None], (b, s, 1)),
            axis=-1)                                           # [B, S, 1]
        x = jnp.where(x < kth, jnp.float32(-1e30), x)

        def one_lane(seed, base, xrow):
            def one_slot(offset, xr):
                key = jax.random.fold_in(
                    jax.random.fold_in(jax.random.PRNGKey(0), seed),
                    base + offset)
                return jnp.argmax(
                    xr + jax.random.gumbel(key, xr.shape, jnp.float32)
                ).astype(jnp.int32)

            return jax.vmap(one_slot)(jnp.arange(s, dtype=jnp.int32), xrow)

        sampled = jax.vmap(one_lane)(seeds, draw_idx, x)       # [B, S]
        return jnp.where((temperature > 0.0)[:, None], sampled, greedy)

    # runtime (not trace-time) all-greedy fast path: an all-greedy batch —
    # the common serving mode — skips per-(lane, slot) key derivation and
    # Gumbel draws entirely; one program serves both cases.
    return jax.lax.cond(jnp.any(temperature > 0.0), stochastic,
                        lambda _: greedy, operand=None)


@functools.lru_cache(maxsize=1)
def _jitted():
    import jax

    # the scope names the sampler's ops in a device trace
    # (docs/OBSERVABILITY.md); the decorator keeps the module's name
    return jax.jit(jax.named_scope("sampler")(_sample_fn))


def sample_tokens(logits, temperature, top_k, seeds, draw_idx) -> np.ndarray:
    """Sample one token per (lane, slot) on device; returns np.int32.

    Args:
      logits: [B, V] or [B, S, V] float logits.
      temperature: [B] float — <= 0 means greedy argmax for that lane.
      top_k: [B] int — 0 disables top-k filtering for that lane.
      seeds: [B] int — per-request RNG seed.
      draw_idx: [B] int — tokens drawn so far by the request; slot s of a
        lane draws with counter `draw_idx + s`.
    Returns [B] (2-D input) or [B, S] (3-D input) sampled token ids.
    """
    squeeze = logits.ndim == 2
    arr = logits[:, None, :] if squeeze else logits
    # args go to the jit raw (np with the right dtypes / device arrays):
    # the C++ dispatch path transfers them far cheaper than per-arg
    # host-side device_put calls — this is the decode hot loop.
    out = _jitted()(
        arr,
        np.asarray(temperature, np.float32),
        np.asarray(top_k, np.int32),
        np.asarray(seeds, np.int32),
        np.asarray(draw_idx, np.int32))
    out = np.asarray(out, np.int32)
    return out[:, 0] if squeeze else out


def pack_lanes(q_lens, kv_lens, rows=None, top_k=0, seeds=0,
               draw_idx=0) -> np.ndarray:
    """The `[B, 6]` int32 lane block (`LANE_COLS`). `rows` is each lane's
    LAST packed row, by default where `ragged_metadata` packs it."""
    q_lens = np.asarray(q_lens, np.int32)
    lanes = np.zeros((q_lens.shape[0], len(LANE_COLS)), np.int32)
    lanes[:, _Q_LEN] = q_lens
    lanes[:, _KV_LEN] = kv_lens
    lanes[:, _ROW] = (np.maximum(np.cumsum(q_lens) - 1, 0) if rows is None
                      else rows)
    lanes[:, _TOP_K], lanes[:, _SEED], lanes[:, _DRAW] = top_k, seeds, draw_idx
    return lanes


def _band_finite(hidden, lanes):
    """`hidden` [T, H] a row a packed token, `lanes` [B, 6]: whether every
    value of each lane's WHOLE packed band (rows `row - q_len + 1 .. row`)
    is finite, [B] bool. An empty lane reads finite."""
    import jax.numpy as jnp

    q_lens, rows = lanes[:, _Q_LEN], lanes[:, _ROW]
    bad = jnp.cumsum(~jnp.isfinite(hidden).all(axis=-1), dtype=jnp.int32)
    bad = jnp.concatenate([jnp.zeros((1,), jnp.int32), bad])
    return bad[rows + 1] == bad[rows + 1 - q_lens]


def step_tail(logits, lanes, temperature):
    """The sampled rows' logits to a round's `[2, B]` int32, traced inside
    the step's one jit: logits [B, V] float32, lane b's LAST packed row's
    (`with_tail` runs the head over those rows alone), `lanes` [B, 6] int32
    (`LANE_COLS`), temperature [B] float32. Row 0 is the token sampled from
    each lane's row (`_sample_fn`, as `sample_tokens` runs it at S == 1),
    row 1 whether every logit of it is finite; an empty lane, whose row is
    some other lane's, reads finite."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope("llama.nan_screen"):
        finite = jnp.isfinite(logits).all(axis=-1) | (lanes[:, _Q_LEN] == 0)
    with jax.named_scope("sampler"):
        picked = _sample_fn(logits[:, None, :], temperature,
                            lanes[:, _TOP_K], lanes[:, _SEED],
                            lanes[:, _DRAW])[:, 0]
    return jnp.stack([picked, finite.astype(jnp.int32)])


def fed_token(lane: int) -> int:
    """The token id that stands for "what `lane` sampled in the engine's
    previous sampled step" (`with_tail` resolves it on the device)."""
    return -(lane + 1)


def with_tail(stack, head):
    """An engine's two halves as its sampled step. `stack` `(*state,
    tokens, q_lens, kv_lens, tables) -> (hidden [T, H], *state)` runs the
    layers; `head` `(state, rows [N, H], lane [N]) -> logits [N, V]
    float32` is the last norm and the output matmul over whatever rows it
    is given, `state` the step's leading arguments as a tuple and `lane`
    the lane each row belongs to. The sampled step is `(*state, tokens,
    lanes, tables, temperature, fed) -> (sampled [2, B], *state)`, for
    `jax.jit` to compile as ONE program: the head runs over each lane's
    LAST packed row alone, `[B, H]`, and `step_tail` samples its `[B, V]`
    logits; no `[T, V]` array is made. Whatever leads the arguments
    (params, pools, adapters, counters) passes through, so donation
    indices stay the engine's.

    `sampled[1]`, a lane's flag, says whether every final hidden value of
    its WHOLE packed band and every logit of its last row is finite: a NaN
    in an early row of a chunk convicts that lane and no other (a hidden
    row that is not finite has no finite logit, so the band's hidden rows
    stand for the band's logits); an empty lane reads finite.

    `fed` `[2, B]` int32 is the `sampled` of the engine's previous call
    (zeros before the first): before the stack, a token `fed_token(b)` is
    replaced by `fed[0, b]`, so a decode lane can take the token it has
    just sampled without the host ever reading it. Same shape and dtype
    every call, and no token is negative unless a caller makes it so:
    still one executable, computing what it always computed."""
    def _ragged_fn(*args):
        import jax
        import jax.numpy as jnp

        # trace-time only: this IS the serving decode program, so it owns
        # the decode_retraces counter the zero-recompile suite asserts on;
        # ragged_retraces pins "ONE executable whatever the batch's
        # composition or a prompt's length"
        monitor.inc("serving.decode_retraces")
        monitor.inc("serving.ragged_retraces")
        *state, tokens, lanes, tables, temperature, fed = args
        with jax.named_scope("llama.feed"):
            lane = jnp.clip(-tokens - 1, 0, fed.shape[1] - 1)
            tokens = jnp.where(tokens < 0, fed[0][lane], tokens)
        hidden, *out = stack(*state, tokens, lanes[:, _Q_LEN],
                             lanes[:, _KV_LEN], tables)
        with jax.named_scope("llama.nan_screen"):
            band = _band_finite(hidden, lanes)
        with jax.named_scope("sampler"):
            last = hidden[lanes[:, _ROW]]                      # [B, H]
        logits = head(tuple(state), last,
                      jnp.arange(last.shape[0], dtype=jnp.int32))  # [B, V]
        sampled = step_tail(logits, lanes, temperature)
        return (sampled.at[1].multiply(band.astype(jnp.int32)), *out)

    # the function's name is the XLA module's: `jit__ragged_fn`, which is
    # how a profile tells the serving step (docs/OBSERVABILITY.md)
    return _ragged_fn


def all_rows(stack, head):
    """The same `stack` and `head` (see `with_tail`) as the program that
    returns every packed row's logits: `(*state, tokens, q_lens, kv_lens,
    tables) -> (logits [T, V] float32, *state)`. An executable of its own
    (`jit__logits_fn`), traced when `ragged_step` first calls it: no round
    of the scheduler does."""
    def _logits_fn(*args):
        from .pallas.paged_attention import ragged_metadata

        monitor.inc("serving.logits_retraces")  # trace-time only
        *state, tokens, q_lens, kv_lens, tables = args
        hidden, *out = stack(*args)
        lane, _pos = ragged_metadata(q_lens, kv_lens, tokens.shape[0])
        return (head(tuple(state), hidden, lane), *out)

    return _logits_fn


def verify_windows(stack, head):
    """The same `stack` and `head` (see `with_tail`) as the speculative
    verify program: `(*state, tokens [B, S], ctx_lens [B], tables) ->
    (logits [B, S, V] float32, *state)`. A case of the ragged step: every
    lane a window of `S` tokens, so the packed buffer is `tokens.reshape(B *
    S)` and every `q_len` is `S`; the head runs over all rows and the logits
    fold back a lane. An executable of its own (`jit__verify_fn`)."""
    def _verify_fn(*args):
        import jax.numpy as jnp

        monitor.inc("serving.verify_retraces")  # trace-time only
        *state, tokens, ctx_lens, tables = args
        b, s = tokens.shape
        hidden, *out = stack(*state, tokens.reshape(b * s),
                             jnp.full((b,), s, jnp.int32), ctx_lens, tables)
        lane = jnp.repeat(jnp.arange(b, dtype=jnp.int32), s)
        return (head(tuple(state), hidden, lane).reshape(b, s, -1), *out)

    return _verify_fn


def call_arrays(tokens, lanes, block_tables, temperature, fed=None):
    """A sampled step's call arrays: the round's four as exact-dtype numpy
    (they go to the jit raw, the C++ dispatch path transfers them far
    cheaper than per-argument host-side `device_put` calls: this is the
    decode loop), then `fed`, the engine's `last_sampled`, as it lies on
    the device (zeros when nothing precedes: lowering, a fresh engine)."""
    lanes = np.asarray(lanes, np.int32)
    if fed is None:
        fed = np.zeros((2, lanes.shape[0]), np.int32)
    return (np.asarray(tokens, np.int32), lanes,
            np.asarray(block_tables, np.int32),
            np.asarray(temperature, np.float32), fed)


def step_args(tokens, q_lens, kv_lens, block_tables):
    """`call_arrays` for greedy lanes sampling their last packed rows, fed
    nothing: what lowers the sampled step at those shapes."""
    q_lens = np.asarray(q_lens, np.int32)
    return call_arrays(tokens, pack_lanes(q_lens, kv_lens), block_tables,
                       np.zeros(q_lens.shape, np.float32))


def ragged_step(engine, tokens, q_lens, kv_lens, block_tables):
    """`EngineCore.ragged_step`, bound by every engine class: the logits
    `[T, V]` of one step over every packed row, for `generate`, proposers,
    probes and checks that sample on the host. The engine's all-rows
    program (`all_rows`), over the state the sampled step leaves and
    takes: `engine._run` threads it."""
    monitor.inc("serving.step.all_rows_calls")
    return engine._run(engine._logits, *(
        np.asarray(a, np.int32)
        for a in (tokens, q_lens, kv_lens, block_tables)))
