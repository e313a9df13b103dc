"""How `correct` is decided for a GLM-MoE-DSA configuration: `check.py`'s
comparison (`Compared`, the served-token gaps, the same signature of
`served_gap`) over the plain reference of THIS architecture,
`reference/glm_moe_dsa_arch.py`. A runner hands this module to
`runners/serve.py`'s `drive` in `check.py`'s place.

The reference gets its weights from the seed (`weights.make_leaf`), never
from the program, and is given the same share of each expert layer (the held
experts of a router of the published width) and the same slice of the
vocabulary.

What it holds on the device, and what it compiles, does not depend on what a
window served, only on the configuration's `check.width`, the widest a
sample can be (the runner samples the requests that fit it):

- one sequence at a time through all the layers, padded to `check.width`;
  its hidden state stays on the device from layer to layer (0.6 GB at
  24,576 positions) with its packed selection (0.08 GB), and a finished
  sequence's last hidden state waits on the host for the head;
- one SUB-BLOCK's weights at a time (attention and the indexer 0.35 GB; the
  dense SwiGLU 0.45 GB; the held routed experts 1.21 GB and the shared expert
  0.08 GB in bf16), made where they are used and dropped (made again for the
  next sequence: a leaf is a draw from the seed); the reference upcasts a
  matrix where it multiplies by it;
- the head runs on the served positions only, `HEAD_ROWS` at a time.

The runner drops the engine's pools AND its weights before this runs
(nothing compared lives in them), so the check has the chip.
"""
from __future__ import annotations

import functools

import check as base            # the benchmark's own; already imported
import jax
import jax.numpy as jnp
import numpy as np

load, Compared, weights = base.load, base.Compared, base.weights
ref = load("reference/glm_moe_dsa_arch.py")

HEAD_ROWS = 256          # served positions through the head at a time
FFN_DENSE = ("mlp.gate_proj.weight", "mlp.up_proj.weight",
             "mlp.down_proj.weight")
ROUTED = ("mlp.gate.weight", "mlp.gate.e_score_correction_bias",
          "mlp.experts.gate_proj.weight", "mlp.experts.up_proj.weight",
          "mlp.experts.down_proj.weight")
SHARED = ("mlp.shared_experts.gate_proj.weight",
          "mlp.shared_experts.up_proj.weight",
          "mlp.shared_experts.down_proj.weight")


def _freeze(cfg):
    """The numbers the reference reads, hashable: a jit's static argument."""
    keep = ("hidden_size", "num_attention_heads", "q_lora_rank",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "index_n_heads", "index_head_dim", "index_topk",
            "indexer_rope_interleave", "rope_interleave", "rms_norm_eps",
            "intermediate_size", "moe_intermediate_size", "n_routed_experts",
            "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "vocab_size")
    return tuple((k, cfg[k]) for k in keep if k in cfg) \
        + (("share", ref.share(cfg)),)


def _thaw(frozen):
    cfg = dict(frozen)
    width, first, count = cfg.pop("share")
    cfg["reduced"] = {"n_routed_experts": {"published": width,
                                           "held": [first, count]}}
    return cfg


@functools.partial(jax.jit, static_argnames=("cfg", "kind", "quant"))
def _attend(x, norm_w, p, cos, sin, carried, at, cfg, kind, quant):
    """`at [N]`: the positions whose rows of the sub-block's output (before
    the residual) and of the selection it attended over are handed back."""
    cfg = _thaw(cfg)
    h = ref.rms_norm(x, norm_w, cfg["rms_norm_eps"])
    o, carried = ref.attention(h, p, cfg, kind, cos, sin, carried, quant)
    return (x + o, carried, jnp.take(o, at, axis=0),
            ref._unpack(jnp.take(carried, at, axis=0)))


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def _dense(x, norm_w, p, cfg, quant):
    a = ref.rms_norm(x, norm_w, _thaw(cfg)["rms_norm_eps"])
    return x + ref.swiglu_rows(a, *(p[k] for k in FFN_DENSE), quant)


@functools.partial(jax.jit, static_argnames=("cfg", "quant"))
def _routed(x, norm_w, p, cfg, quant):
    cfg = _thaw(cfg)
    a = ref.rms_norm(x, norm_w, cfg["rms_norm_eps"])
    return a, x + ref.routed_moe(a, p, cfg, quant), ref.bf16_flips(a, p, cfg)


@functools.partial(jax.jit, static_argnames=("quant",))
def _shared(a, x, p, quant):
    return x + ref.shared_expert(a, p, quant)


def compile_ahead(cfg, watched, dtype="bfloat16"):
    """The reference's programs over one sequence of `check.width`
    positions (`watched` of them watched), lowered and compiled from shapes
    alone: no weight is made and nothing is held on the device.
    `reference_hidden` then finds them lowered and compiled (the process
    keeps a program by its shapes; the persistent compile cache keeps it
    for the next run). A runner calls this off its serving thread while its
    traffic's set-up keeps the device busy, so that no run waits for them
    after its window: 34-38 s where the cache is empty, and their tracing
    where it is not."""
    width, h = cfg["check"]["width"], cfg["hidden_size"]
    shapes, frozen = ref.param_shapes(cfg), _freeze(cfg)
    kinds = ref.layer_kinds(cfg)

    def arr(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(tuple(shape), dt)

    def leaves(i, names):
        return {n: arr(shapes[f"model.layers.{i}.{n}"][0], dtype)
                for n in names}

    x, norm_w = arr((width, h)), arr((h,), dtype)
    table = arr((width, cfg["qk_rope_head_dim"] // 2))
    at = arr((watched,), jnp.int32)
    for kind, names, carried in (
            (ref.FULL, ref.ATTENTION + ref.INDEXER, None),
            (ref.SHARED, ref.ATTENTION,
             arr((width, width // 32), jnp.uint32))):
        if kind in kinds:
            _attend.lower(x, norm_w, leaves(kinds.index(kind), names), table,
                          table, carried, at, frozen, kind, None).compile()
    dense = cfg["first_k_dense_replace"]
    if dense:
        _dense.lower(x, norm_w, leaves(0, FFN_DENSE), frozen, None).compile()
    if dense < len(kinds):
        _routed.lower(x, norm_w, leaves(dense, ROUTED), frozen,
                      None).compile()
        _shared.lower(x, x, leaves(dense, SHARED), None).compile()


def reference_hidden(cfg, seed, rows, dtype, quant=None, watch=None):
    """rows: a list of id arrays [S_r] (each its own padded width) -> the
    last layer's output before the final norm, float32 [S_r, H] each, ON THE
    HOST. A sequence at a time through every layer, a sub-block's weights
    at a time: they are made again for each sequence (a leaf is a draw from
    the seed). `watch`: a list of positions a row; then also, a row and a
    layer, `(chosen [N, S_r] bool, out [N, H])`, the selection the layer
    attended over at each of those positions and its attention sub-block's
    output there, for `decode_witness_gap`."""
    shapes = ref.param_shapes(cfg)
    frozen = _freeze(cfg)
    kinds = ref.layer_kinds(cfg)
    flips = np.zeros((len(kinds),))

    def leaf(name):
        return weights.make_leaf(seed, name, *shapes[name], dtype)

    def leaves(pre, names):
        return {n: leaf(pre + n) for n in names}

    xs, watched = [], []
    for r, ids in enumerate(rows):
        at = jnp.asarray(watch[r] if watch else [0], jnp.int32)
        watched.append([])
        embed = leaf("model.embed_tokens.weight")
        x = jnp.take(embed, jnp.asarray(ids), axis=0).astype(jnp.float32)
        del embed
        cos, sin = ref.rope_tables(cfg, len(ids))
        carried = None
        for i, kind in enumerate(kinds):
            pre = f"model.layers.{i}."
            p = leaves(pre, ref.ATTENTION
                       + (ref.INDEXER if kind == ref.FULL else ()))
            # a `full` layer replaces the carried selection: it is handed
            # none, so each kind is one program
            x, carried, out, chosen = _attend(
                x, leaf(pre + "input_layernorm.weight"), p, cos, sin,
                carried if kind == ref.SHARED else None, at, frozen, kind,
                quant)
            del p           # a sub-block's weights go before the next are made
            watched[-1].append((np.asarray(chosen), np.asarray(out)))
            norm_w = leaf(pre + "post_attention_layernorm.weight")
            if i < cfg["first_k_dense_replace"]:
                x = _dense(x, norm_w, leaves(pre, FFN_DENSE), frozen, quant)
                continue
            p = leaves(pre, ROUTED)
            a, x, flipped = _routed(x, norm_w, p, frozen, quant)
            flips[i] += float(flipped) / len(rows)
            del p
            x = _shared(a, x, leaves(pre, SHARED), quant)
            del a
        # np.array, a copy: where host and device share memory (a CPU
        # rehearsal) np.asarray is a view that keeps the device's array alive
        xs.append(np.array(x))
        del x, carried      # or they stay on the device beside the next one
    print("    reference" + (f" ({quant})" if quant else "") + ": rows whose "
          "top-k set changes when the layer's input is rounded to bf16, by "
          "layer: " + ", ".join(f"{100 * f:.2f} %" for f in flips), flush=True)
    return (xs, watched) if watch else xs


def decode_witness_gap(watched, witness):
    """How far the program's DECODE rows lie from the reference's, where a
    check has replayed them (`engine.attention_witness`): `witness` is, a
    sampled request (or None), `{"at" [N] positions, "idx" [L, N, K], "n"
    [L, N], "out" [L, N, H]}`; `watched` is `reference_hidden`'s at the same
    positions. `(miss, error)`, each the WORST over requests, positions and
    layers: `miss`, the share of the reference's selection at a position
    that the program's attention was not given there; `error`, the norm of
    the difference of the attention sub-blocks' outputs there over the norm
    of the reference's. A wrong set moves `error` by about sqrt(2 miss) (an
    output is close to a mean over the set's rows), far more than rounding
    does. None where no request had a witness."""
    miss, error = [], []
    for layers, w in zip(watched, witness):
        if w is None:
            continue
        miss.append([[1.0 - chosen[j, w["idx"][i, j, :w["n"][i, j]]].sum()
                      / max(chosen[j].sum(), 1)
                      for j in range(len(w["at"]))]
                     for i, (chosen, _) in enumerate(layers)])
        error.append([(np.linalg.norm(w["out"][i] - out, axis=-1)
                       / np.linalg.norm(out, axis=-1)).tolist()
                      for i, (_, out) in enumerate(layers)])
    if not miss:
        return None
    for name, rows in (("selection the program was not given", miss),
                       ("relative error of the attention output", error)):
        print(f"    decode witness, {name}: worst by request and layer, % "
              + "; ".join(", ".join(f"{100 * max(layer):.2f}"
                                    for layer in req) for req in rows)
              + f"; over {sum(len(r[0]) for r in rows)} decode positions",
              flush=True)
    worst = lambda rows: float(max(max(max(l) for l in r)      # noqa: E731
                                   for r in rows))
    return worst(miss), worst(error)


def _padded(cfg, samples):
    """Each sample's ids, zero-padded to the configuration's `check.width`:
    every sample one width, so one set of programs, the same in every run."""
    width = int(cfg["check"]["width"])
    rows = []
    for p, t in samples:
        n = len(p) + len(t)
        if n > width:
            raise ValueError(f"a sample of {n} positions is wider than "
                             f"check.width {width}")
        ids = np.zeros((width,), np.int32)
        ids[:n] = list(p) + list(t)
        rows.append(ids)
    return rows


def served_gap(cfg, seed, dtype, samples, pad_to, control=None, witness=None,
               found=None):
    """`check.served_gap` for this architecture: samples [(prompt ids,
    served token ids)] -> (widest gap, mean gap, count), a gap being how far
    the served (greedy) token's logit lies below the reference's best at its
    position, in units of the position's logit std over the vocabulary.

    `control="ref-int8"`: in place of the served tokens, the tokens the
    int8 reference puts first at the same positions. `witness`: a sample's
    replayed decode rows as `decode_witness_gap` takes them (or None);
    `found["decode_witness"]` is then its answer."""
    rows = _padded(cfg, samples)      # `pad_to` is the harness's: not used
    print(f"    reference widths {[len(r) for r in rows]}", flush=True)
    low = (reference_hidden(cfg, seed, rows, dtype, "int8")
           if control == "ref-int8" else None)
    if witness is None:
        xs = reference_hidden(cfg, seed, rows, dtype)
    else:
        xs, watched = reference_hidden(
            cfg, seed, rows, dtype,
            watch=[[0] if w is None else list(w["at"]) for w in witness])
        found["decode_witness"] = decode_witness_gap(watched, witness)
    shapes = ref.param_shapes(cfg)
    norm_w = weights.make_leaf(seed, "model.norm.weight",
                               *shapes["model.norm.weight"], dtype)
    head_w = weights.make_leaf(seed, "lm_head.weight",
                               *shapes["lm_head.weight"], dtype)
    frozen = _freeze(cfg)

    @functools.partial(jax.jit, static_argnames=("quant",))
    def head_stats(x, toks, norm_w, head_w, quant=None):
        """x [rows, H], toks [rows] -> per row (best logit, the logit of
        `toks`, std over the vocabulary, the best token). The weights are
        arguments: closed over, they are compiled into the program as a
        constant no compile cache keeps."""
        logits = ref.head_logits(x, norm_w, head_w, _thaw(frozen), quant)
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
    norm_w = weights.make_leaf(seed, "model.norm.weight",
                               *shapes["model.norm.weight"], dtype)
    head_w = weights.make_leaf(seed, "lm_head.weight",
                               *shapes["lm_head.weight"], dtype)
    return jnp.stack([ref.head_logits(x, norm_w, head_w, cfg, quant)
                      for x in xs])
