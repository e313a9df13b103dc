#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that paddle_tpu starts on the chip.

    python chip_smoke.py            # on a machine with a TPU; anything else fails

One process, no children. It drives the system's main path once at the
full width of Llama-2-7B (hidden 4096, intermediate 11008, 32 heads of 128,
vocab 32000, bf16; depth cut to what the chip holds, and printed), through
the entry points a user calls, and checks what comes out:

- serving: `ServingFrontend(engine).submit(...)` / `run_until_idle()` over
  `LlamaInferenceEngine` with a paged cache (block 16, context 2048), mixed
  prompt lengths so chunked prefill and decode lanes share dispatches; every
  request FINISHED with its token count, no fault or retrace counter moved,
  the lowered ragged step holds the Pallas kernel, and logits and tokens
  agree with the model's own dense float32 forward over the same tokens;
- the engine variants (`kv_bits=8`, `weight_only=int8|fp8|int4`) serve too;
- training: the `bench.py train_mfu` step for a few steps on one batch;
- kernels: every Pallas entry point at its 7B-width shape against its own
  XLA composite;
- with four or more devices: the same serving through
  `shard_engine(engine, tp=4)` and the train step sharded dp x mp.

Any check that fails raises and the run ends non-zero; there is no
`try/except` around a phase. The last line of stdout is one JSON object,
`{"ok": true, "device": {...}}`, printed only when every phase passed.

    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse

is the named rehearsal: the same code at a tiny size on the CPU with the
kernels in the Pallas interpreter (add
`XLA_FLAGS=--xla_force_host_platform_device_count=8` to rehearse the
four-device phases). It proves the command runs before chip time is spent,
prints counts only, and never prints the result line. It is never entered
by default or on failure.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.metadata
import json
import sys
import time
from types import SimpleNamespace

# Llama-2-7B widths (`models.llama.llama_7b_shaped`); no width is ever cut.
FULL = SimpleNamespace(
    hidden=4096, inter=11008, heads=32, vocab=32000, max_pos=2048,
    block_size=16, blocks_per_seq=128, lanes=8, chunk=64,
    prompts=(23, 200, 64, 331, 9), new_tokens=(24, 32, 16, 24, 32),
    small_layers=2, train_seq=2048, train_batch=2, train_steps=4,
    moe_rows=1024, moe_experts=8, moe_capacity=256,
    # the serving cells' own ragged step (benchmark/configs/
    # mistral-7b-v0.3-serve.json): T, lanes, table width, kv heads, group,
    # head dim, block size, most live pages a lane
    cell=(96, 32, 128, 8, 4, 128, 16, 14))
TINY = SimpleNamespace(
    hidden=256, inter=512, heads=4, vocab=512, max_pos=128,
    block_size=16, blocks_per_seq=8, lanes=4, chunk=12,
    prompts=(5, 40, 12, 21), new_tokens=(6, 8, 4, 8),
    small_layers=2, train_seq=128, train_batch=2, train_steps=4,
    moe_rows=32, moe_experts=4, moe_capacity=16,
    cell=(20, 4, 16, 2, 4, 128, 16, 3))

# --- tolerances -------------------------------------------------------------
# All are relative to the largest magnitude of the reference they bound.
#
# The engine computes in bf16: 8 significant bits, unit roundoff 2**-9. The
# weights are rounded to it once and the activations after each of a layer's
# seven matmuls; the errors add roughly in quadrature over depth, and the
# largest of 32000 logits sits ~4 sigma out. The v5e run measured the figure
# recorded in CHANGES.md (PR 21); the bound is about three times that, and
# an order of magnitude under what fp8 arithmetic (roundoff 2**-4) would give.
LOGIT_TOL = 0.03
# Engine variants add quantization noise on top. These bound blunders (a
# wrong scale, a swapped nibble give errors of order 1), not quantization
# quality: each kernel's exact maths is pinned against its XLA composite on
# the same quantized operands in the kernel phase.
VARIANT_TOL = {"kv_bits=8": 0.06, "weight_only=int8": 0.06,
               "weight_only=fp8": 0.25, "weight_only=int4": 0.6}
# A kernel against its composite on the same inputs, the composite at the
# highest matmul precision: what separates them is the output's own rounding
# (bf16, 2**-9), summation order, and where each rounds its probabilities.
KERNEL_TOL = 0.02


class SmokeFailure(AssertionError):
    pass


def check(ok, what):
    """Every acceptance condition goes through here: a false one ends the
    run (the exception is never caught)."""
    if not ok:
        raise SmokeFailure(what)
    print(f"    ok  {what}", flush=True)


# --- compile accounting -------------------------------------------------------

class Phases:
    """Names the phases and, per phase, what the program's own compile
    record (`paddle_tpu.observability.compile_trace`, fed by JAX's events)
    counted: programs compiled, how many came out of the persistent cache,
    and the seconds tracing, lowering and in `backend_compile` (which wraps
    the cache lookup, so a warm run shows as fewer seconds). Seconds are
    printed on the chip only."""

    def __init__(self, on_chip):
        self.on_chip = on_chip
        self.done = []

    @property
    def compile_s(self):
        from paddle_tpu.framework import monitor

        return monitor.get("compile.backend_s")

    @contextlib.contextmanager
    def __call__(self, name):
        from paddle_tpu.framework import monitor

        print(f"[{name}]", flush=True)
        keys = ("programs", "cache_hits", "trace_s", "lower_s", "backend_s")
        before = [monitor.get("compile." + k) for k in keys]
        t0 = time.perf_counter()
        yield
        n, hits, trace_s, lower_s, backend_s = (
            monitor.get("compile." + k) - b for k, b in zip(keys, before))
        line = (f"[{name}] passed: {n} programs compiled, {hits} from the "
                "cache")
        if self.on_chip:
            import jax

            peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
            line += (f", trace {trace_s:.1f} s, lowering {lower_s:.1f} s, "
                     f"compile {backend_s:.1f} s, "
                     f"wall {time.perf_counter() - t0:.1f} s, device 0 "
                     f"peak so far {peak / 2**30:.2f} GiB")
        print(line, flush=True)
        self.done.append(name)


# --- model, reference, serving -----------------------------------------------

def build_model(z, layers):
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    return LlamaForCausalLM(LlamaConfig(
        vocab_size=z.vocab, hidden_size=z.hidden,
        intermediate_size=z.inter, num_hidden_layers=layers,
        num_attention_heads=z.heads, max_position_embeddings=z.max_pos))


def build_engine(z, model, **mode):
    from paddle_tpu.inference.llama_runner import LlamaInferenceEngine

    # one pool page over the lanes' full contexts: the scheduler's pad block
    return LlamaInferenceEngine(
        model, max_batch_size=z.lanes,
        num_blocks=z.lanes * z.blocks_per_seq + 1,
        block_size=z.block_size, max_blocks_per_seq=z.blocks_per_seq,
        dtype="bfloat16", **mode)


def serving_depth(z, bytes_limit):
    """Layers the serving model keeps on this chip. Per parameter the device
    holds 4 B of the float32 model (the reference forward runs on it) and
    2 B of the engine's bf16 copy; per layer the paged pool adds K and V for
    every lane's full context. That sum stays under 60 % of the device's
    memory — the rest is XLA's temporaries, the logits and the reference's
    activations."""
    layer = 4 * z.hidden * z.hidden + 3 * z.hidden * z.inter
    pool = 2 * z.lanes * z.blocks_per_seq * z.block_size * z.hidden * 2
    fixed = 2 * z.vocab * z.hidden * 6
    return max(1, min(32, int((0.6 * bytes_limit - fixed)
                              // (6 * layer + pool))))


@contextlib.contextmanager
def xla_only():
    """Trace with every Pallas gate closed (the reference's arithmetic must
    share nothing with the kernels it judges)."""
    from paddle_tpu.framework import flags

    old = flags.get_flags(["use_pallas", "pallas_interpret"])
    flags.set_flags({"use_pallas": False, "pallas_interpret": False})
    try:
        yield
    finally:
        flags.set_flags(old)


def dense_logits(model, ids):
    """The model's own full forward, `LlamaForCausalLM.forward`, over whole
    sequences [N, S] -> [N, S, V]: float32 weights, the highest matmul
    precision, XLA composites only, no cache."""
    import jax

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit import functional_call, state_arrays

    def fwd(params, ids):
        return functional_call(model, params, Tensor(ids))._data

    with xla_only(), jax.default_matmul_precision("highest"):
        return jax.jit(fwd)(state_arrays(model), ids)


def lowered_custom_calls(engine, T):
    """`tpu_custom_call` sites in the lowered ragged step: the Pallas
    kernels, as opposed to `paged_attention_ragged_ref` and the XLA gemms."""
    import numpy as np

    import bench
    from paddle_tpu.ops.sampling import step_args

    B, W = engine.max_batch_size, engine.manager.max_blocks_per_seq
    fn, lead = engine.cost_card_args("ragged")
    i32 = np.int32
    return bench._count_pallas_calls(fn, *lead, *step_args(
        np.zeros(T, i32), np.zeros(B, i32), np.zeros(B, i32),
        np.zeros((B, W), i32)))


def replay_logits(engine, seq, n_prompt, n_decode, T, chunk):
    """Prefill-then-decode through the cache, outside the scheduler, on the
    all-rows program of its step (`ragged_step`: the same stack and head,
    the same fixed shapes, every row's logits): `seq[:n_prompt]` prefilled in
    `chunk`-token ragged steps on lane 0, then `n_decode` teacher-forced
    decode steps. Returns [1 + n_decode, V]: the first-token logits and one
    row per decode step."""
    import numpy as np

    mgr = engine.manager
    B, W = engine.max_batch_size, mgr.max_blocks_per_seq
    sid = 1 << 30                     # outside any live request's id space
    rows = []

    def step(toks, kv_len):
        tokens = np.zeros(T, np.int32)
        tokens[:len(toks)] = toks
        q_lens = np.zeros(B, np.int32)
        kv_lens = np.zeros(B, np.int32)
        q_lens[0], kv_lens[0] = len(toks), kv_len
        tables = np.zeros((B, W), np.int32)
        tables[0] = mgr.block_table_array([sid])[0]
        out = engine.ragged_step(tokens, q_lens, kv_lens, tables)
        return np.asarray(out[len(toks) - 1])

    try:
        done = 0
        while done < n_prompt:
            n = min(chunk, n_prompt - done)
            if done:
                mgr.append_tokens(sid, n)
            else:
                mgr.allocate(sid, n)
            last = step(seq[done:done + n], done + n)
            done += n
        rows.append(last)
        for i in range(n_decode):
            mgr.append_tokens(sid, 1)
            rows.append(step(seq[n_prompt + i:n_prompt + i + 1],
                             n_prompt + i + 1))
    finally:
        mgr.free(sid)
    return np.stack(rows)


def serve(engine, z, prompts, new_tokens, on_chip, min_custom_calls):
    """Serve `prompts` through `ServingFrontend` and check the run itself:
    statuses, token counts, fault and retrace counters, kernel presence, and
    that chunked prefill and decode did share dispatches. Returns the
    generated tokens per request and the packed token budget T."""
    from paddle_tpu.framework import monitor
    from paddle_tpu.serving import RequestStatus, ServingFrontend
    from paddle_tpu.serving.metrics import ServingMetrics

    class Metrics(ServingMetrics):
        mixed_steps = 0

        def on_ragged_step(self, prefill_tokens, decode_lanes):
            super().on_ragged_step(prefill_tokens, decode_lanes)
            self.mixed_steps += bool(prefill_tokens and decode_lanes)

    counters = ("serving.step_faults", "serving.isolated_faults",
                "serving.engine_restarts")
    before = {c: monitor.get(c) or 0 for c in counters}
    fe = ServingFrontend(engine, metrics=Metrics(),
                         prefill_chunk_tokens=z.chunk)
    T = fe.scheduler.ragged_tokens
    calls = lowered_custom_calls(engine, T)
    print(f"    lowered ragged step: T={T} tokens, {calls} tpu_custom_call")
    if on_chip:
        check(calls >= min_custom_calls,
              f"the lowered ragged step holds >= {min_custom_calls} "
              "Pallas custom calls")

    # warm-up: one request longer than a chunk compiles the step, the NaN
    # screen, the row gather and the sampler
    warm = fe.submit(list(range(1, z.chunk + 4)), max_new_tokens=2)
    fe.run_until_idle()
    check(warm.status is RequestStatus.FINISHED, "warm-up request FINISHED")
    retraces = monitor.get("serving.ragged_retraces")

    handles = [fe.submit(p, max_new_tokens=n)
               for p, n in zip(prompts, new_tokens)]
    steps = fe.run_until_idle()
    print(f"    {len(handles)} requests, prompts "
          f"{[len(p) for p in prompts]}, {steps} scheduler steps, "
          f"{fe.metrics.mixed_steps} with prefill and decode in one "
          "dispatch")
    check(all(h.status is RequestStatus.FINISHED for h in handles),
          "every request FINISHED")
    check([len(h.tokens) for h in handles] == list(new_tokens),
          f"token counts as requested {list(new_tokens)}")
    for c in counters:
        check((monitor.get(c) or 0) == before[c], f"{c} did not move")
    check(monitor.get("serving.ragged_retraces") == retraces,
          "serving.ragged_retraces 0 after warm-up")
    check(fe.metrics.mixed_steps > 0,
          "chunked prefill and decode lanes shared a dispatch")
    return [h.tokens for h in handles], T


def reference_rows(model, prompts, tokens):
    """Dense-forward logits for every generated position of every request:
    row i of request r is the distribution its i-th new token was drawn
    from, given the prompt and the tokens the ENGINE generated before it."""
    import numpy as np

    full = [list(p) + list(t) for p, t in zip(prompts, tokens)]
    width = -(-max(map(len, full)) // 64) * 64     # one shape, one compile
    ids = np.zeros((len(full), width), np.int32)   # causal: right pad is inert
    for r, f in enumerate(full):
        ids[r, :len(f)] = f
    logits = dense_logits(model, ids)
    return [np.asarray(logits[r, len(p) - 1:len(p) - 1 + len(t)])
            for r, (p, t) in enumerate(zip(prompts, tokens))]


def check_tokens(tokens, ref_rows, tol, what):
    """Greedy tokens equal the reference's argmax, except where the
    reference's own top-two gap is inside the tolerance (each of the two
    logits may be off by `tol`, so a gap under 2*tol decides nothing)."""
    ties = total = 0
    for toks, rows in zip(tokens, ref_rows):
        scale = abs(rows).max()
        for tok, row in zip(toks, rows):
            total += 1
            if tok != row.argmax():
                gap = (row.max() - row[tok]) / scale
                check(gap <= 2 * tol,
                      f"{what}: token {tok} vs reference {row.argmax()} is "
                      f"a tie (gap {gap:.4f} of the logit range)")
                ties += 1
    print(f"    {what}: {total} tokens equal the dense forward's argmax, "
          f"{ties} of them by the tie rule")


def check_logits(engine, z, T, prompt, toks, rows, tol, what):
    """First-token and a few decode-step logits, prefill-then-decode through
    the cache, against the dense forward's rows for the same tokens."""
    import numpy as np

    n = min(4, len(toks) - 1)
    got = replay_logits(engine, list(prompt) + list(toks), len(prompt), n,
                        T, z.chunk)
    check(np.isfinite(got).all() and got.shape == (n + 1, z.vocab),
          f"{what}: logits finite, shape {got.shape}")
    err = float(abs(got - rows[:n + 1]).max() / abs(rows[:n + 1]).max())
    check(err <= tol, f"{what}: prompt {len(prompt)}, first token + {n} "
                      f"decode steps within {tol} of the dense forward "
                      f"(measured {err:.4f})")


def serving_phase(z, depth, on_chip, devices):
    import numpy as np

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, z.vocab, n).tolist() for n in z.prompts]
    model = build_model(z, depth)
    model.eval()
    engine = build_engine(z, model)
    tokens, T = serve(engine, z, prompts, z.new_tokens, on_chip, 1)
    rows = reference_rows(model, prompts, tokens)
    check_tokens(tokens, rows, LOGIT_TOL, "one chip")
    longest = max(range(len(prompts)), key=lambda r: len(prompts[r]))
    for r in {0, longest}:
        check_logits(engine, z, T, prompts[r], tokens[r], rows[r],
                     LOGIT_TOL, "one chip")
    if len(devices) >= 4:
        tp4_phase(z, model, engine, prompts, tokens, rows, T, on_chip)


def tp4_phase(z, model, engine, prompts, tokens, rows, T, on_chip):
    """The same requests through the same frontend over
    `shard_engine(engine, tp=4)`: tokens equal to the one-chip run under the
    same tie rule — where a stream first departs from it, the dense forward
    (which judges both: their prefix is shared up to there) must call that
    position a tie."""
    from paddle_tpu.serving import shard_engine

    print("  [tp=4]", flush=True)
    sharded = shard_engine(engine, tp=4)
    got, _ = serve(sharded, z, prompts, z.new_tokens, on_chip, 1)
    departed = 0
    for r, (a, b) in enumerate(zip(got, tokens)):
        i = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
        if i is None:
            continue
        departed += 1
        row = rows[r][i]
        gap = (row.max() - min(row[a[i]], row[b[i]])) / abs(rows[r]).max()
        check(gap <= 2 * LOGIT_TOL,
              f"tp=4 request {r} departs from one chip at token {i} on a "
              f"tie (gap {gap:.4f})")
    print(f"    {len(got) - departed}/{len(got)} streams token-equal to the "
          "one-chip run")
    if departed:
        rows = reference_rows(model, prompts, got)
    check_tokens(got, rows, LOGIT_TOL, "tp=4")
    check_logits(sharded, z, T, prompts[0], got[0], rows[0], LOGIT_TOL,
                 "tp=4")


def variants_phase(z, model, on_chip):
    """`kv_bits=8` and the three weight-only modes: each builds, serves
    through the frontend (the Pallas kernels in the lowered step: the ragged
    attention, plus the four projection gemms of the scanned layer when the
    weights are quantized) and stays near the dense forward."""
    import numpy as np

    rng = np.random.default_rng(1)
    # the short prompt first: it decodes while the long one still prefills
    prompts = [rng.integers(1, z.vocab, n).tolist()
               for n in (7, 2 * z.chunk + 9)]
    # one teacher-forced sequence judges every variant: a prompt longer
    # than a chunk and five tokens after it, against ONE dense forward
    seq = rng.integers(1, z.vocab, z.chunk + 14).tolist()
    n = len(seq) - 5
    rows = np.asarray(dense_logits(model, np.asarray([seq]))[0, n - 1:])
    for mode, calls in (({"kv_bits": 8}, 1), ({"weight_only": "int8"}, 5),
                        ({"weight_only": "fp8"}, 5),
                        ({"weight_only": "int4"}, 5)):
        name = "%s=%s" % next(iter(mode.items()))
        print(f"  [{name}]", flush=True)
        engine = build_engine(z, model, **mode)
        _, T = serve(engine, z, prompts, (8, 6), on_chip, calls)
        check_logits(engine, z, T, seq[:n], seq[n:], rows,
                     VARIANT_TOL[name], name)
        del engine
        gc.collect()


# --- training -------------------------------------------------------------------

def run_steps(z, step, state, ids, labels):
    """`train_steps` donated steps on the one batch; losses finite and
    falling."""
    import numpy as np

    losses = []
    for i in range(z.train_steps):
        loss, *state = step(*state, float(i + 1), ids, labels)
        losses.append(float(loss))
    print(f"    losses {[round(x, 4) for x in losses]}")
    check(all(np.isfinite(losses)), f"{z.train_steps} steps, losses finite")
    check(all(b < a for a, b in zip(losses, losses[1:])),
          "loss decreases every step on the repeated batch")
    return losses


def train_phase(z, model, on_chip, devices):
    """The `bench.py train_mfu` step as it is — `functional_call` +
    `jax.value_and_grad` + AdamW under one donated `jax.jit` — for a few
    steps on one repeated batch."""
    import jax
    import numpy as np

    import bench

    rng = np.random.default_rng(0)
    ids = rng.integers(0, z.vocab, (z.train_batch, z.train_seq))
    labels = rng.integers(0, z.vocab, (z.train_batch, z.train_seq))
    model.train()
    train_step, *state = bench.build_train_step(model)
    step = jax.jit(train_step, donate_argnums=(0, 1, 2))
    calls = bench._count_pallas_calls(step, *state, 1.0, ids, labels)
    print(f"    lowered train step: L={z.small_layers} "
          f"seq={z.train_seq} b={z.train_batch}, {calls} tpu_custom_call")
    if on_chip:
        check(calls >= 1, "the lowered train step holds Pallas custom calls")
    losses = run_steps(z, step, state, ids, labels)
    del state
    gc.collect()
    if len(devices) >= 4:
        train4_phase(z, model, devices, ids, labels, losses, on_chip)


def train4_phase(z, model, devices, ids, labels, losses_one_chip, on_chip):
    """The same train step sharded dp=2 x mp=2 by GSPMD, parameters and
    moments in the `_param_spec` layout of `__graft_entry__.py`."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    import bench
    from __graft_entry__ import _param_spec
    from paddle_tpu.observability import comms

    print("  [dp=2 x mp=2]", flush=True)
    mesh = Mesh(np.asarray(devices[:4]).reshape(2, 2), ("dp", "mp"))
    train_step, *state = bench.build_train_step(model)
    whole = sum(a.nbytes for a in state[0].values())
    specs = {k: NamedSharding(mesh, _param_spec(k, P)) for k in state[0]}
    # leaf by leaf, dropping each unsharded original as it goes: the whole
    # state starts on chip 0 and must not sit there twice
    state = [{k: jax.device_put(tree.pop(k), specs[k]) for k in list(tree)}
             for tree in state]
    data = NamedSharding(mesh, P("dp", None))
    ids, labels = jax.device_put(ids, data), jax.device_put(labels, data)
    per_device = {}
    for a in state[0].values():
        for sh in a.addressable_shards:
            per_device[sh.device.id] = (per_device.get(sh.device.id, 0)
                                        + sh.data.nbytes)
    print(f"    parameter bytes per device {per_device} of {whole} whole")
    check(len(per_device) == 4 and max(per_device.values()) < 0.75 * whole,
          "parameters are spread over four devices")
    step = jax.jit(train_step, donate_argnums=(0, 1, 2),
                   in_shardings=(specs, specs, specs, None, data, data),
                   out_shardings=(None, specs, specs, specs)
                   ).lower(*state, 1.0, ids, labels).compile()
    hlo = step.as_text()
    census = {k: v["ops"] for k, v in comms.hlo_comm_census(hlo).items()}
    print(f"    compiled step: {hlo.count('tpu_custom_call')} "
          f"tpu_custom_call, collectives {census}")
    losses = run_steps(z, step, state, ids, labels)
    check(abs(losses[0] - losses_one_chip[0]) <= 1e-2 * losses_one_chip[0],
          f"first loss {losses[0]:.4f} equals the one-chip step's "
          f"{losses_one_chip[0]:.4f} to 1 %")


# --- kernels ----------------------------------------------------------------------

def kernel_cases(z):
    """Every Pallas entry point a gate selects on TPU, at the shape it meets
    at these widths: (name, make, kernel, composite). `make(key)` builds the
    arguments with `jax.random` only, so `jax.eval_shape(make, key)` gives
    their shapes at no cost — `tests/test_pallas_kernels.py` lowers every
    kernel here for TPU from exactly that."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference import kv_quant
    from paddle_tpu.models.llama import _apply_rope_fn
    from paddle_tpu.nn.functional.attention import _sdpa_fn
    from paddle_tpu.nn.functional.norm import _rms_norm_fn
    from paddle_tpu.nn.quant import pack_int4, per_channel_quantize, \
        unpack_int4
    from paddle_tpu.ops.pallas import bias_act, flash_attention, fused_moe
    from paddle_tpu.ops.pallas import paged_attention as pk
    from paddle_tpu.ops.pallas import quant_matmul as qm
    from paddle_tpu.ops.pallas import rms_norm, rope

    bf, f32, i32 = jnp.bfloat16, jnp.float32, jnp.int32
    H, D = z.heads, z.hidden // z.heads
    B, W, BS = z.lanes, z.blocks_per_seq, z.block_size
    T, NB, cap = z.lanes + z.chunk, z.lanes * z.blocks_per_seq, W * BS
    b, s = z.train_batch, z.train_seq

    def normal(key, i, shape, dtype=bf):
        return jax.random.normal(jax.random.fold_in(key, i), shape,
                                 f32).astype(dtype)

    def pool(key):
        # every lane owns W distinct pages, scattered over the pool
        tables = ((jnp.arange(NB, dtype=i32) * 7 + 3) % NB).reshape(B, W)
        return (normal(key, 1, (NB, H, BS, D)),
                normal(key, 2, (NB, H, BS, D)), tables)

    def ragged_args(key):
        # lane 0: a prefill chunk that ends at the full context; then
        # decode lanes from a short to a full cache; the last lane empty
        kc, vc, tables = pool(key)
        q_lens = jnp.asarray([z.chunk] + [1] * (B - 2) + [0], i32)
        kv_lens = jnp.asarray(
            [cap] + [1 + (cap - 1) * i // (B - 2) for i in range(1, B - 1)]
            + [0], i32)
        lane, pos = pk.ragged_metadata(q_lens, kv_lens, T)
        return (normal(key, 0, (T, H, D)), kc, vc, tables, kv_lens, lane,
                pos)

    def cell_args(key):
        # the benchmark's serving step: a table far wider than any lane's
        # live pages, its dead entries all the scheduler's one pad block;
        # lane 0 a prefill chunk behind prior context, decode lanes from
        # one token to the most a lane holds, the last lane empty
        t, lanes, width, kvh, g, d, bs, live = z.cell
        chunk, most = t - lanes, live * bs
        q_lens = jnp.asarray([chunk] + [1] * (lanes - 2) + [0], i32)
        kv_lens = jnp.asarray(
            [most] + [1 + (most - 1) * i // (lanes - 2)
                      for i in range(1, lanes - 1)] + [0], i32)
        nb = lanes * live + 1
        # 5 shares no factor with 32 * 14 (or the rehearsal's 4 * 3): every
        # live page a block of its own, scattered over the pool
        own = 1 + (jnp.arange(lanes * live, dtype=i32) * 5 + 3) \
            % (lanes * live)
        tables = jnp.where(
            jnp.arange(live, dtype=i32)[None] * bs < kv_lens[:, None],
            own.reshape(lanes, live), 0)
        tables = jnp.pad(tables, ((0, 0), (0, width - live)))
        lane, pos = pk.ragged_metadata(q_lens, kv_lens, t)
        return (normal(key, 0, (t, kvh * g, d)),
                normal(key, 1, (nb, kvh, bs, d)),
                normal(key, 2, (nb, kvh, bs, d)), tables, kv_lens, lane, pos)

    def write_args(key):
        # the rows the cells' step writes: a chunk that crosses blocks,
        # decode tokens, guard slots at the tail
        _q, kc, vc, tables, _kv_lens, lane, pos = cell_args(key)
        rows = (pos.shape[0],) + kc.shape[1:2] + kc.shape[3:]
        return (normal(key, 3, rows), normal(key, 4, rows), kc, vc, tables,
                lane, pos)

    def quantized(make):
        def args(key):
            q, kc, vc, *rest = make(key)
            return (q,) + kv_quant.quantize_kv(kc) \
                + kv_quant.quantize_kv(vc) + tuple(rest)
        return args

    def ragged_q(fn):
        return lambda q, kq, ks, vq, vs, *rest: fn(
            q, kq, vq, *rest, k_scale=ks, v_scale=vs)

    def decode_args(key, window=1):
        # every lane `window` tokens (one, or the S of a verify pass, all
        # of which the cache already counts), contexts from that to full
        kc, vc, tables = pool(key)
        lens = jnp.asarray([window + (cap - window) * i // (B - 1)
                            for i in range(B)], i32)
        return normal(key, 0, (B * window, H, D)), kc, vc, tables, lens

    def window_args(key):
        # the verify step's composition: 4 tokens a lane
        q, kc, vc, tables, lens = decode_args(key, 4)
        lane, pos = pk.ragged_metadata(jnp.full((B,), 4, i32), lens, B * 4)
        return q, kc, vc, tables, lens, lane, pos

    def with_grads(attention):
        def run(q, k, v, g):
            out, vjp = jax.vjp(attention, q, k, v)
            return (out,) + vjp(g)
        return run

    def gemm_args(algo, n, k):
        def make(key):
            w = normal(key, 1, (n, k), f32) * k ** -0.5
            wq, scale = per_channel_quantize(w, algo)
            if algo == "weight_only_int4":
                wq = pack_int4(wq)
            return normal(key, 0, (T, k)), wq, scale
        return make

    def gemm_ref(int4):
        def ref(x, wq, scale):
            wq = unpack_int4(wq) if int4 else wq
            return x @ (wq.astype(x.dtype)
                        * scale[:, None].astype(x.dtype)).T
        return ref

    def moe_idx():
        # N rows over E experts, slot = arrival order; every 13th dropped
        r = jnp.arange(z.moe_rows, dtype=i32)
        return r % z.moe_experts, jnp.where(r % 13 == 0, -1,
                                            r // z.moe_experts)

    cases = [
        ("paged_attention_ragged bf16", ragged_args,
         pk.paged_attention_ragged, pk.paged_attention_ragged_ref),
        ("paged_attention_ragged int8-KV", quantized(ragged_args),
         ragged_q(pk.paged_attention_ragged),
         ragged_q(pk.paged_attention_ragged_ref)),
        ("paged_attention_ragged bf16, the cells' shape", cell_args,
         pk.paged_attention_ragged, pk.paged_attention_ragged_ref),
        ("paged_attention_ragged int8-KV, the cells' shape",
         quantized(cell_args), ragged_q(pk.paged_attention_ragged),
         ragged_q(pk.paged_attention_ragged_ref)),
        ("kv_write_ragged bf16, the cells' shape", write_args,
         pk.write_kv_to_cache_ragged, pk.write_kv_to_cache_ragged_ref),
        ("paged_attention_ragged bf16, a verify window a lane", window_args,
         pk.paged_attention_ragged, pk.paged_attention_ragged_ref),
        ("paged_attention (q_len 1 of the ragged kernel)", decode_args,
         pk.paged_attention, pk.paged_attention_ref),
        # one sequence of the batch: the composite holds the [H, S, S]
        # scores and their cotangents in f32, 13 GiB of temporaries at b=2
        # (the kernel at the full batch is inside the train step above)
        ("flash_attention fwd+bwd causal",
         lambda key: tuple(normal(key, i, (1, s, H, D)) for i in range(4)),
         with_grads(lambda q, k, v: flash_attention._flash_bshd(
             q, k, v, True)),
         with_grads(lambda q, k, v: _sdpa_fn(q, k, v, None, True, None,
                                             False))),
        ("rms_norm",
         lambda key: (normal(key, 0, (b, s, z.hidden)),
                      normal(key, 1, (z.hidden,))),
         lambda x, w: rms_norm.rms_norm(x, w, 1e-6),
         lambda x, w: _rms_norm_fn(x, w, 1e-6)),
        ("fused_rope",
         lambda key: (normal(key, 0, (b, s, H, D)),
                      normal(key, 1, (b, s, H, D)),
                      jnp.cos(normal(key, 2, (s, D // 2), f32)),
                      jnp.sin(normal(key, 2, (s, D // 2), f32))),
         rope.fused_rope,
         lambda q, k, c, si: _apply_rope_fn(q, k, c, si, 0)),
        ("swiglu(x, y)",
         lambda key: (normal(key, 0, (b * s, z.inter)),
                      normal(key, 1, (b * s, z.inter))),
         bias_act.swiglu,
         lambda x, y: (jax.nn.silu(x.astype(f32))
                       * y.astype(f32)).astype(x.dtype)),
        ("swiglu(packed)",
         lambda key: (normal(key, 0, (b * s, 2 * z.inter)),),
         bias_act.swiglu,
         lambda x: bias_act._ref_bias_act(
             x, jnp.zeros(x.shape[-1:], x.dtype), "swiglu")),
        ("fused_bias_act gelu",
         lambda key: (normal(key, 0, (b * s, z.inter)),
                      normal(key, 1, (z.inter,))),
         lambda x, bias: bias_act.fused_bias_act(x, bias, "gelu"),
         lambda x, bias: bias_act._ref_bias_act(x, bias, "gelu")),
    ]
    for algo, label in (("weight_only_int8", "int8"), ("fp8", "fp8"),
                        ("weight_only_int4", "int4")):
        int4 = label == "int4"
        for what, n, k in (("gate_up", 2 * z.inter, z.hidden),
                           ("down", z.hidden, z.inter)):
            cases.append((f"quant_matmul {label} {what} [{n}, {k}]",
                          gemm_args(algo, n, k),
                          qm.quant_matmul_int4 if int4 else qm.quant_matmul,
                          gemm_ref(int4)))
    cases += [
        ("moe_dispatch",
         lambda key: (normal(key, 0, (z.moe_rows, z.hidden)),) + moe_idx(),
         lambda x, e, p: fused_moe.moe_dispatch(x, e, p, z.moe_experts,
                                                z.moe_capacity),
         lambda x, e, p: fused_moe.xla_dispatch(x, e, p, z.moe_experts,
                                                z.moe_capacity)),
        ("moe_gather",
         lambda key: (normal(key, 0, (z.moe_experts, z.moe_capacity,
                                      z.hidden)),) + moe_idx(),
         fused_moe.moe_gather, fused_moe.xla_gather),
    ]
    return cases


def kernels_phase(z, phase):
    import jax
    import jax.numpy as jnp

    @jax.jit
    def errors(got, want):
        return [jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)).max()
                / jnp.abs(w.astype(jnp.float32)).max()
                for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want))]

    # an RBG key: under the package's x64 mode the default threefry
    # generator compiles 64-bit counter arithmetic for every big array,
    # 13-15 s of compile per case on the v5e against under 3 s for this
    key = jax.random.key(0, impl="rbg")
    for name, make, kernel, composite in kernel_cases(z):
        s0 = phase.compile_s
        args = jax.jit(make)(key)
        got = jax.jit(kernel)(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(composite)(*args)
        check(jax.tree.map(lambda a: (a.shape, a.dtype), got)
              == jax.tree.map(lambda a: (a.shape, a.dtype), want),
              f"{name}: shapes and dtypes as the composite's")
        errs = [float(e) for e in errors(got, want)]
        check(all(e <= KERNEL_TOL for e in errs),
              f"{name}: within {KERNEL_TOL} of its composite (measured "
              f"{', '.join(f'{e:.5f}' for e in errs)})"
              + (f", compile {phase.compile_s - s0:.1f} s"
                 if phase.on_chip else ""))
        del args, got, want
        gc.collect()


# --- entry ------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny CPU rehearsal (needs JAX_PLATFORMS=cpu); "
                         "prints counts, never a result")
    args = ap.parse_args(argv)

    import jax

    backend = jax.default_backend()
    if args.rehearse:
        if backend != "cpu":
            sys.exit("chip_smoke --rehearse is the CPU rehearsal: run it "
                     "with JAX_PLATFORMS=cpu")
    elif backend != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (default backend "
                 f"{backend!r}). Nothing was run.")
    on_chip = not args.rehearse

    import paddle_tpu
    from paddle_tpu.framework import compile_cache, flags

    cache_dir = compile_cache.configure()
    devices = jax.devices()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    versions = {p: importlib.metadata.version(p)
                for p in ("jax", "jaxlib", "libtpu")}
    print(f"device: platform={dev.platform} device_kind={dev.device_kind!r} "
          f"count={len(devices)}")
    print(f"versions: {versions} python {sys.version.split()[0]}")
    print(f"compile cache: {cache_dir}")
    if on_chip:
        z = FULL
        limit = int(dev.memory_stats()["bytes_limit"])
        depth = serving_depth(z, limit)
        print(f"memory: {limit / 2**30:.2f} GiB a device")
    else:
        z, depth = TINY, 3
        flags.set_flags({"pallas_interpret": True})
        print("REHEARSAL on the CPU at a tiny size: counts only, no result")
    print(f"model: Llama widths hidden={z.hidden} intermediate={z.inter} "
          f"heads={z.heads} vocab={z.vocab} bf16; depth {depth} of 32 for "
          f"serving, {z.small_layers} of 32 for the engine variants and "
          "training", flush=True)
    paddle_tpu.seed(0)

    phase = Phases(on_chip)
    with phase("serving"):
        serving_phase(z, depth, on_chip, devices)
    gc.collect()
    small = build_model(z, z.small_layers)
    with phase("engine variants"):
        variants_phase(z, small, on_chip)
    with phase("training"):
        train_phase(z, small, on_chip, devices)
    del small
    gc.collect()
    with phase("kernels"):
        kernels_phase(z, phase)

    print(f"phases passed: {', '.join(phase.done)}"
          + (" (tp=4 serving and the dp x mp train step included)"
             if len(devices) >= 4 else ""))
    if on_chip:
        print(json.dumps({"ok": True, "device": device}))
    else:
        print("rehearsal passed; this is not a result")


if __name__ == "__main__":
    main()
