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
"""
from __future__ import annotations

import functools

import numpy as np

__all__ = ["sample_tokens"]


def _sample_fn(logits, temperature, top_k, seeds, draw_idx):
    """logits [B,S,V] f32; temperature [B]; top_k [B]; seeds/draw_idx [B]."""
    import jax
    import jax.numpy as jnp

    from ..framework import monitor

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
