"""How `correct` is decided: the timed path's own products against the plain
reference (`reference/llama_arch.py`), each number beside its limit. The
limits and the readings they were set from are in each configuration's file
under `check`.

The reference gets its weights from the seed (`weights.make_leaf`), one layer
at a time, never from the program.
"""
from __future__ import annotations

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))


def load(path, name=None):
    """Import one file of the benchmark by path (its directories are data,
    found by name; none is a package)."""
    path = os.path.join(_HERE, path)
    name = name or "benchmark_" + os.path.basename(path).replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = load("reference/llama_arch.py")
weights = load("weights.py")


class Compared:
    """The numbers compared in one run, each beside its limit."""

    def __init__(self):
        self.rows = []

    def add(self, name, value, limit):
        ok = bool(np.isfinite(value)) and value <= limit
        self.rows.append((name, float(value), float(limit), ok))
        return ok

    @property
    def ok(self):
        return bool(self.rows) and all(r[3] for r in self.rows)

    def print(self, file=None):
        for name, value, limit, ok in self.rows:
            print(f"compare {name}: {value:.6g} (limit {limit:.6g}) "
                  f"{'ok' if ok else 'NOT OK'}", file=file, flush=True)

    def as_dict(self):
        return {name: {"value": value, "limit": limit}
                for name, value, limit, _ in self.rows}


def _leaves_f32(cfg, seed, dtype, prefix):
    shapes = ref.param_shapes(cfg)
    return {n: weights.make_leaf(seed, n, s, k, dtype).astype(jnp.float32)
            for n, (s, k) in shapes.items() if n.startswith(prefix)}


def reference_logits(cfg, seed, ids, dtype, quant=None):
    """ids [N, S] -> float32 logits [N, S, V] of the reference on the
    seed's weights (made in `dtype`, upcast: the weights ARE bf16-valued,
    so their rounding is no part of any error), one layer at a time: each
    layer's weights are made inside the one compiled program that applies
    it, from the layer's name hashes."""
    n, s = ids.shape
    lo, hi = weights.split_seed(seed)
    cos, sin = ref.rope_tables(cfg, s)
    embed = _leaves_f32(cfg, seed, dtype, "llama.embed_tokens.")
    x = jnp.take(embed["llama.embed_tokens.weight"], jnp.asarray(ids), axis=0)
    del embed
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    shapes = ref.layer_shapes(cfg["hidden_size"], cfg["intermediate_size"],
                              cfg["num_key_value_heads"] * d)
    names = sorted(shapes)

    @jax.jit
    def step(x, lo, hi, hashes):
        p = {k: weights.leaf(lo, hi, hashes[i], shapes[k][0], shapes[k][1],
                             jnp.dtype(dtype)).astype(jnp.float32)
             for i, k in enumerate(names)}
        return jax.vmap(lambda x: ref.layer(x, p, cfg, cos, sin, quant))(x)

    for i in range(cfg["num_hidden_layers"]):
        hashes = jnp.asarray([weights.name_hash(f"llama.layers.{i}.{k}")
                              for k in names], jnp.int32)
        x = step(x, lo, hi, hashes)
    norm_w = _leaves_f32(cfg, seed, dtype, "llama.norm.")["llama.norm.weight"]
    head_w = _leaves_f32(cfg, seed, dtype, "lm_head.")["lm_head.weight"]
    return jax.jit(lambda x, a, b: ref.head_logits(x, a, b, cfg, quant))(
        x, norm_w, head_w)


def served_gap(cfg, seed, dtype, samples, pad_to, control=None):
    """samples: [(prompt ids, served token ids)]. The gap by which a served
    token's logit lies below the reference's best at its position, in units
    of that position's logit spread (std over the vocabulary): the widest
    over every served token of the sample, their mean (a gap is 0 wherever
    the served token IS the reference's best; the mean grows with the
    square of the arithmetic's error, so it separates precisions better
    than the widest, which swings), and the count. Greedy tokens only.

    `control="ref-int8"`: in place of the served tokens, the tokens the
    int8 reference puts first at the same positions."""
    width = -(-max(len(p) + len(t) for p, t in samples) // pad_to) * pad_to
    ids = np.zeros((len(samples), width), np.int32)
    for r, (p, t) in enumerate(samples):
        ids[r, :len(p) + len(t)] = list(p) + list(t)
    logits = reference_logits(cfg, seed, ids, dtype)
    low = (reference_logits(cfg, seed, ids, dtype, "int8")
           if control == "ref-int8" else None)
    worst, total, count = 0.0, 0.0, 0
    for r, (p, t) in enumerate(samples):
        rows = np.asarray(logits[r, len(p) - 1:len(p) - 1 + len(t)])
        toks = np.asarray(t)
        if low is not None:
            toks = np.asarray(
                low[r, len(p) - 1:len(p) - 1 + len(t)]).argmax(-1)
        picked = rows[np.arange(len(toks)), toks]
        gap = (rows.max(-1) - picked) / rows.std(-1)
        worst, total = max(worst, float(gap.max())), total + float(gap.sum())
        count += len(toks)
    return worst, total / count, count


# --- training -----------------------------------------------------------------

def tree_norms(tree):
    return {k: float(v) for k, v in jax.jit(
        lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                   for k, v in t.items()})(tree).items()}


def reference_train(cfg, opt, seed, dtype, batches, quant=None):
    """The reference through the first AdamW step (loss, gradient, update)
    and, on the updated parameters, the loss of each further batch: with two
    batches it follows the program's first two losses and its first update.
    One gradient at a time is all that fits in float32 beside the
    parameters without the reference's peak passing the program's own.
    Returns {"loss": [...], "grad_norm": {leaf: ...} of the first gradient,
    "delta_norm": {leaf: ...} of the parameters' change in the first step}."""
    shapes = ref.param_shapes(cfg)
    params = _leaves_f32(cfg, seed, dtype, "")

    def mean_loss(p, ids, labels):
        return jnp.mean(jax.vmap(
            lambda i, l: ref.loss(p, i, l, cfg, quant))(ids, labels))

    update = jax.jit(lambda p, g: ref.adamw_leaf(
        p, g, jnp.zeros_like(g), jnp.zeros_like(g), 1.0, opt)[0],
        donate_argnums=(0,))
    ids, labels = batches[0]
    loss, g = jax.jit(jax.value_and_grad(mean_loss))(params, ids, labels)
    out = {"loss": [float(loss)], "grad_norm": tree_norms(g)}
    for k in sorted(params):
        params[k] = update(params[k], g.pop(k))
    forward = jax.jit(mean_loss)
    for ids, labels in batches[1:]:
        out["loss"].append(float(forward(params, ids, labels)))
    sub = jax.jit(lambda a, b: jnp.sqrt(jnp.sum(jnp.square(a - b))))
    out["delta_norm"] = {}
    for k in sorted(params):
        s, kind = shapes[k]
        p0 = weights.make_leaf(seed, k, s, kind, dtype).astype(jnp.float32)
        out["delta_norm"][k] = float(sub(params.pop(k), p0))
    return out


def worst_leaf_gap(got: dict, want: dict):
    """The largest gap between a leaf's norm and the reference's norm of
    it, measured against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some leaves are all but zero)."""
    med = float(np.median(list(want.values())))
    worst, where = 0.0, None
    for k, w in want.items():
        gap = abs(got[k] - w) / max(w, med)
        if gap > worst:
            worst, where = gap, k
    return worst, where


def compare_train(compared, got, want, limits):
    """got / want: reference_train-shaped readings of the program and of
    the reference."""
    for i, (a, b) in enumerate(zip(got["loss"], want["loss"]), 1):
        compared.add(f"loss_step{i}_rel_gap", abs(a - b) / abs(b),
                     limits["loss_rel_gap"])
    g, where = worst_leaf_gap(got["grad_norm"], want["grad_norm"])
    print(f"    worst first-gradient leaf: {where}")
    compared.add("first_grad_norm_worst_leaf_gap", g,
                 limits["grad_norm_leaf_gap"])
    d, where = worst_leaf_gap(got["delta_norm"], want["delta_norm"])
    print(f"    worst parameter-change leaf: {where}")
    compared.add("param_change_norm_worst_leaf_gap", d,
                 limits["param_change_leaf_gap"])
