"""How `correct` is decided for a DeepSeek-V3-architecture configuration:
`check.py`'s comparison (`Compared`, the served-token gaps, the same
signature of `served_gap`) over the plain reference of THIS architecture,
`reference/deepseek_v3_arch.py`. A runner hands this module to
`runners/serve.py`'s `drive` in `check.py`'s place.

The reference gets its weights from the seed (`weights.make_leaf`), a layer
at a time, never from the program. It never holds logits for a whole
sequence (10,000 positions x 128,256 columns are 5 GB): the head runs on the
served positions only, a block of rows at a time, and only the three numbers
a position needs are kept.

It runs beside the built engine, in the 3 GiB that leaves, so what it holds
on the device may not grow with what the window served: the sequences'
hidden states wait on the host between layers, one sequence is on the device
at a time, and the head's weights (0.49 GiB) are made only after the last
layer's are gone. A width of the deployment's `context_tokens` fits
(PERF.md section 2), and nothing longer can be served.
"""
from __future__ import annotations

import functools

import check as base            # the benchmark's own; already imported
import jax
import jax.numpy as jnp
import numpy as np

load, Compared, weights = base.load, base.Compared, base.weights
ref = load("reference/deepseek_v3_arch.py")

HEAD_ROWS = 256          # served positions through the head at a time
VOCAB_CHUNK = 16384      # ... and columns of the head at a time


def _leaf(seed, dtype, name, shapes):
    shape, kind = shapes[name]
    return weights.make_leaf(seed, name, shape, kind, dtype)


def reference_hidden(cfg, seed, ids, dtype, quant=None):
    """ids [N, S] -> the last layer's output before the final norm, float32
    [S, H] a sequence, ON THE HOST: one layer's weights at a time, and
    beside them one sequence on the device at a time (in, then out); the
    others wait in host memory. So what the device holds does not grow with
    the number of sequences sampled, and with their width only by one
    sequence and the step's own temporaries."""
    shapes = ref.param_shapes(cfg)
    cos, sin = ref.rope_tables(cfg, ids.shape[1])
    embed = _leaf(seed, dtype, "model.embed_tokens.weight", shapes)
    # np.array, a copy: where host and device share memory (a CPU rehearsal)
    # np.asarray is a view that keeps the device's array alive
    xs = [np.array(jnp.take(embed, jnp.asarray(row), axis=0)
                   .astype(jnp.float32)) for row in ids]
    del embed
    step = jax.jit(lambda x, p: ref.layer(x, p, cfg, cos, sin, quant,
                                          flips=True))
    flips = []
    for i in range(cfg["num_hidden_layers"]):
        p = {k: _leaf(seed, dtype, f"model.layers.{i}.{k}", shapes)
             for k in ref.layer_shapes(cfg, i)}
        share = []
        for r, x in enumerate(xs):
            y, s = step(x, p)
            xs[r] = np.array(y)
            share.append(float(s))
            del y       # or it stays on the device beside the next sequence
        flips.append(float(np.mean(share)))
        del p
    print("    reference" + (f" ({quant})" if quant else "") + ": rows whose "
          "top-k set changes when the layer's input is rounded to bf16, by "
          "layer: " + ", ".join(f"{100 * f:.2f} %" for f in flips), flush=True)
    return xs


def served_gap(cfg, seed, dtype, samples, pad_to, control=None):
    """`check.served_gap` for this architecture: samples [(prompt ids,
    served token ids)] -> (widest gap, mean gap, count), a gap being how far
    the served (greedy) token's logit lies below the reference's best at its
    position, in units of the position's logit std over the vocabulary.

    `control="ref-int8"`: in place of the served tokens, the tokens the
    int8 reference puts first at the same positions."""
    width = -(-max(len(p) + len(t) for p, t in samples) // pad_to) * pad_to
    ids = np.zeros((len(samples), width), np.int32)
    for r, (p, t) in enumerate(samples):
        ids[r, :len(p) + len(t)] = list(p) + list(t)
    # the layers first, with nothing of the head on the device: a width of
    # `context_tokens` has to fit beside the engine (PERF.md section 2)
    low = (reference_hidden(cfg, seed, ids, dtype, "int8")
           if control == "ref-int8" else None)
    xs = reference_hidden(cfg, seed, ids, dtype)
    shapes = ref.param_shapes(cfg)
    norm_w = _leaf(seed, dtype, "model.norm.weight", shapes)
    head_w = _leaf(seed, dtype, "lm_head.weight", shapes)

    chunk = ref._block(cfg["vocab_size"], VOCAB_CHUNK)

    @functools.partial(jax.jit, static_argnames=("quant",))
    def head_stats(x, toks, norm_w, head_w, quant=None):
        """x [rows, H], toks [rows] -> per row (best logit, the logit of
        `toks`, std over the vocabulary, the best token), the head taken
        `chunk` columns at a time. The head's weights are arguments: closed
        over, they are compiled into the program as a constant of 0.5 GB,
        which no compile cache keeps, so every run compiled it anew."""
        rows = x.shape[0]

        def columns(j, acc):
            top, best, picked, s1, s2 = acc
            lo = j * chunk
            logits = ref.head_logits(
                x, norm_w, jax.lax.dynamic_slice_in_dim(head_w, lo, chunk, 1),
                cfg, quant)
            here = (toks >= lo) & (toks < lo + chunk)
            mine = jnp.take_along_axis(
                logits, jnp.clip(toks - lo, 0, chunk - 1)[:, None], -1)[:, 0]
            m = logits.max(-1)
            return (jnp.maximum(top, m),
                    jnp.where(m > top, (lo + jnp.argmax(logits, -1)).astype(jnp.int32),
                              best),
                    jnp.where(here, mine, picked),
                    s1 + logits.sum(-1), s2 + jnp.square(logits).sum(-1))

        zero = jnp.zeros((rows,), jnp.float32)
        top, best, picked, s1, s2 = jax.lax.fori_loop(
            0, cfg["vocab_size"] // chunk, columns,
            (zero - jnp.inf, jnp.zeros((rows,), jnp.int32), zero, zero, zero))
        mean = s1 / cfg["vocab_size"]
        return top, picked, jnp.sqrt(s2 / cfg["vocab_size"] - mean * mean), best

    stats = functools.partial(head_stats, norm_w=norm_w, head_w=head_w)

    def served_rows(xs):
        for r, (p, t) in enumerate(samples):
            rows = xs[r][len(p) - 1:len(p) - 1 + len(t)]
            pad = (-len(t)) % HEAD_ROWS
            rows = np.pad(rows, ((0, pad), (0, 0)))
            yield r, len(t), rows.reshape(-1, HEAD_ROWS, rows.shape[-1])

    toks = {r: np.asarray(t, np.int64) for r, (p, t) in enumerate(samples)}
    if low is not None:
        for r, n, blocks in served_rows(low):
            none = jnp.zeros((HEAD_ROWS,), jnp.int32)
            toks[r] = np.concatenate(
                [np.asarray(stats(b, none, quant="int8")[3]) for b in blocks])[:n]
        del low
    worst, total, count = 0.0, 0.0, 0
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
        count += n
    return worst, total / count, count


def reference_logits(cfg, seed, ids, dtype, quant=None):
    """ids [N, S] -> float32 logits [N, S, V]; for small sizes (tests)."""
    xs = reference_hidden(cfg, seed, ids, dtype, quant)
    shapes = ref.param_shapes(cfg)
    norm_w = _leaf(seed, dtype, "model.norm.weight", shapes)
    head_w = _leaf(seed, dtype, "lm_head.weight", shapes)
    return jnp.stack([ref.head_logits(x, norm_w, head_w, cfg, quant)
                      for x in xs])
