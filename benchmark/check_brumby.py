"""How `correct` is decided for a Brumby configuration: `check.py`'s
comparison (`Compared`, the served-token gaps, the same signature of
`served_gap`) over the plain reference of THIS architecture,
`reference/brumby_arch.py`, which computes the retention layer in its
attention form: no state, no feature map, nothing of the program's. A runner
hands this module to `runners/serve.py`'s `drive` in `check.py`'s place.

The reference gets its weights from the seed (`weights.make_leaf`), never
from the program; a layer's gate biases are `reference.gate_bias` of one such
draw (`leaf`), which is how the runner makes the program's too.

What it holds on the device does not depend on what a window served, only on
the configuration's `check.width_step`:

- one sequence at a time through all the layers, padded to a multiple of
  `check.width_step`; a finished sequence's last hidden state waits on the
  host for the head;
- one SUB-BLOCK's weights at a time (retention 0.126 GB, the SwiGLU 0.535 GB
  in bf16), made where they are used and dropped (made again for the next
  sequence: a leaf is a draw from the seed); the reference upcasts a matrix
  where it multiplies by it;
- the head runs on the served positions only, `HEAD_ROWS` at a time.

The runner drops the engine's state before this runs (nothing compared lives
in it), so the check has what the weights leave of the chip.
"""
from __future__ import annotations

import functools

import check as base            # the benchmark's own; already imported
import jax
import jax.numpy as jnp
import numpy as np

load, Compared, weights = base.load, base.Compared, base.weights
ref = load("reference/brumby_arch.py")

HEAD_ROWS = 256          # served positions through the head at a time


def leaf(seed, name, shape, kind, dtype):
    """One leaf of the seed's weights: `weights.make_leaf`, and for a gate
    bias `reference.gate_bias` of a float32 draw under the bias's name."""
    if kind == "bias":
        return ref.gate_bias(weights.make_leaf(seed, name, shape, "matrix",
                                               "float32"), weights.STD)
    return weights.make_leaf(seed, name, shape, kind, dtype)


def _leaves(seed, dtype, shapes, prefix, names):
    return {n: leaf(seed, prefix + n, *shapes[prefix + n], dtype)
            for n in names}


def _freeze(cfg):
    """The numbers the reference reads, hashable: a jit's static argument."""
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "head_dim", "intermediate_size", "rms_norm_eps", "vocab_size",
            "retention_scale", "retention_eps")
    return tuple((k, cfg[k]) for k in keep if cfg.get(k) is not None)


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def _retain(x, p, cos, sin, cfg, quant):
    return ref.retention_block(x, p, dict(cfg), cos, sin, quant)


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def _mlp(x, p, cfg, quant):
    return ref.mlp_block(x, p, dict(cfg), quant)


def reference_hidden(cfg, seed, rows, dtype, quant=None):
    """rows: a list of id arrays [S_r] (each its own padded width) -> the
    last layer's output before the final norm, float32 [S_r, H] each, ON THE
    HOST. A sequence at a time through every layer, a sub-block's weights
    at a time."""
    shapes = ref.param_shapes(cfg)
    frozen = _freeze(cfg)
    xs = []
    for ids in rows:
        name = "model.embed_tokens.weight"
        embed = leaf(seed, name, *shapes[name], dtype)
        x = jnp.take(embed, jnp.asarray(ids), axis=0).astype(jnp.float32)
        del embed
        cos, sin = ref.rope_tables(cfg, len(ids))
        for i in range(cfg["num_hidden_layers"]):
            pre = f"model.layers.{i}."
            p = _leaves(seed, dtype, shapes, pre, ref.RETENTION)
            x = _retain(x, p, cos, sin, frozen, quant)
            del p           # a sub-block's weights go before the next are made
            p = _leaves(seed, dtype, shapes, pre, ref.MLP)
            x = _mlp(x, p, frozen, quant)
            del p
        # np.array, a copy: where host and device share memory (a CPU
        # rehearsal) np.asarray is a view that keeps the device's array alive
        xs.append(np.array(x))
        del x               # or it stays on the device beside the next sequence
    return xs


def _padded(cfg, samples, pad_to):
    """Each sample's ids, zero-padded to its own multiple of the width step
    (the configuration's `check.width_step`, else `pad_to`). Padding lies
    AFTER the served tokens and the layer is causal: it moves nothing."""
    step = int((cfg.get("check") or {}).get("width_step") or pad_to)
    rows = []
    for p, t in samples:
        ids = np.zeros((-(-(len(p) + len(t)) // step) * step,), np.int32)
        ids[:len(p) + len(t)] = list(p) + list(t)
        rows.append(ids)
    return rows


def served_gap(cfg, seed, dtype, samples, pad_to, control=None):
    """`check.served_gap` for this architecture: samples [(prompt ids,
    served token ids)] -> (widest gap, mean gap, count), a gap being how far
    the served (greedy) token's logit lies below the reference's best at its
    position, in units of the position's logit std over the vocabulary.

    `control="ref-int8"`: in place of the served tokens, the tokens the
    int8 reference puts first at the same positions."""
    rows = _padded(cfg, samples, pad_to)
    print(f"    reference widths {[len(r) for r in rows]}", flush=True)
    low = (reference_hidden(cfg, seed, rows, dtype, "int8")
           if control == "ref-int8" else None)
    xs = reference_hidden(cfg, seed, rows, dtype)
    shapes = ref.param_shapes(cfg)
    norm_w = leaf(seed, "model.norm.weight", *shapes["model.norm.weight"],
                  dtype)
    head_w = leaf(seed, "lm_head.weight", *shapes["lm_head.weight"], dtype)
    frozen = _freeze(cfg)

    @functools.partial(jax.jit, static_argnames=("quant",))
    def head_stats(x, toks, norm_w, head_w, quant=None):
        """x [rows, H], toks [rows] -> per row (best logit, the logit of
        `toks`, std over the vocabulary, the best token). The weights are
        arguments: closed over, they are compiled into the program as a
        constant no compile cache keeps."""
        logits = ref.head_logits(x, norm_w, head_w, dict(frozen), quant)
        mine = jnp.take_along_axis(
            logits, jnp.clip(toks, 0, logits.shape[-1] - 1)[:, None], -1)[:, 0]
        return (logits.max(-1), mine, logits.std(-1),
                jnp.argmax(logits, -1).astype(jnp.int32))

    stats = functools.partial(head_stats, norm_w=norm_w, head_w=head_w)

    def served_rows(xs):
        for r, (p, t) in enumerate(samples):
            out = xs[r][len(p) - 1:len(p) - 1 + len(t)]
            out = np.pad(out, ((0, (-len(t)) % HEAD_ROWS), (0, 0)))
            yield r, len(t), out.reshape(-1, HEAD_ROWS, out.shape[-1])

    toks = {r: np.asarray(t, np.int64) for r, (p, t) in enumerate(samples)}
    if low is not None:
        none = jnp.zeros((HEAD_ROWS,), jnp.int32)
        for r, n, blocks in served_rows(low):
            toks[r] = np.concatenate(
                [np.asarray(stats(b, none, quant="int8")[3])
                 for b in blocks])[:n]
        del low
    worst, total, count, apart = 0.0, 0.0, 0, []
    for r, n, blocks in served_rows(xs):
        t = np.full((blocks.shape[0] * HEAD_ROWS,), -1, np.int64)
        t[:n] = toks[r]
        for b, tb in zip(blocks, t.reshape(-1, HEAD_ROWS)):
            top, picked, std, _ = (np.asarray(a)[tb >= 0] for a in
                                   stats(b, jnp.asarray(tb, jnp.int32)))
            gap = (top - picked) / std
            if gap.size:
                worst = max(worst, float(gap.max()))
                total += float(gap.sum())
                apart.extend(gap[gap > 0].tolist())
        count += n
    # what the mean is made of: the rows at which the served token is not
    # the reference's best
    apart = np.sort(np.asarray(apart))[::-1]
    print(f"    served tokens that are not the reference's best: "
          f"{apart.size} of {count}, their gaps' sum {apart.sum():.3f} stds, "
          f"the 8 widest {[round(float(g), 3) for g in apart[:8]]}, "
          f"{int((apart > 0.1).sum())} over 0.1 std", flush=True)
    stats_now = jax.devices()[0].memory_stats() or {}
    if stats_now.get("peak_bytes_in_use"):
        print(f"    device memory peak so far "
              f"{stats_now['peak_bytes_in_use'] / 2 ** 30:.3f} GiB of "
              f"{stats_now.get('bytes_limit', 0) / 2 ** 30:.3f}", flush=True)
    return worst, total / count, count


def reference_logits(cfg, seed, ids, dtype, quant=None):
    """ids [N, S] -> float32 logits [N, S, V]; for small sizes (tests)."""
    xs = reference_hidden(cfg, seed, [np.asarray(r) for r in ids], dtype,
                          quant)
    shapes = ref.param_shapes(cfg)
    norm_w = leaf(seed, "model.norm.weight", *shapes["model.norm.weight"],
                  dtype)
    head_w = leaf(seed, "lm_head.weight", *shapes["lm_head.weight"], dtype)
    return jnp.stack([ref.head_logits(x, norm_w, head_w, cfg, quant)
                      for x in xs])
