"""Serving smoke: drive 16 short requests through the continuous-batching
frontend on CPU and assert (1) every request completes, (2) the decode path
performs ZERO recompiles after warmup, (3) serving metrics are present and
monotone, then re-run the SAME trace through a speculative-decoding
frontend (n-gram proposer + batched verify) over the same weights and
assert (4) greedy token-for-token parity with the non-speculative run and
(5) zero steady-state retraces on the verify/sample paths too.
Tier-1-safe: finishes well under 60 s on CPU.

Usage:
    python tools/serving_smoke.py [--engine llama|mlp] [--requests 16]

Exit code 0 on success; prints one JSON line with the run's metrics.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

_LLAMA_MODEL = None


def build_engine(kind: str):
    if kind == "mlp":
        from paddle_tpu.serving import MLPLMEngine

        return MLPLMEngine(vocab_size=64, hidden=16, max_batch_size=4,
                           num_blocks=48, block_size=4, max_blocks_per_seq=8)
    # ONE model for every engine this process builds: the speculative pass
    # asserts token parity against the plain pass, so both must serve the
    # same weights
    global _LLAMA_MODEL
    if _LLAMA_MODEL is None:
        import paddle_tpu as paddle
        from paddle_tpu.models import llama_tiny

        paddle.seed(0)   # reproducible acceptance numbers across runs
        _LLAMA_MODEL = llama_tiny(vocab=64, layers=2, hidden=32, heads=2,
                                  seq=64)
        _LLAMA_MODEL.eval()
    from paddle_tpu.inference import LlamaInferenceEngine

    return LlamaInferenceEngine(_LLAMA_MODEL, max_batch_size=4,
                                num_blocks=48, block_size=4,
                                max_blocks_per_seq=8)


def drive(fe, warm_prompts, prompts, monitor):
    """Warmup (compile coverage) -> counter reset -> run `prompts`.
    Returns the request handles of the measured run."""
    from paddle_tpu.serving import RequestStatus

    warm = [fe.submit(p, max_new_tokens=3) for p in warm_prompts]
    fe.run_until_idle(max_steps=500)
    assert all(h.status is RequestStatus.FINISHED for h in warm), warm
    # the ragged step (chunked prefill + decode fused) always compiles
    # on a fresh engine; the speculative pass compiles verify instead
    assert monitor.get("serving.ragged_retraces") >= 1 \
        or monitor.get("serving.verify_retraces") >= 1, "never compiled?"

    for c in ("serving.decode_retraces", "serving.ragged_retraces",
              "serving.verify_retraces", "serving.sample_retraces"):
        monitor.reset(c)
    fe.metrics.reset_window()   # warmup latencies are not the smoke's
    handles = [fe.submit(p, max_new_tokens=g) for p, g in prompts]
    fe.run_until_idle(max_steps=2000)
    bad = [h for h in handles if h.status is not RequestStatus.FINISHED]
    assert not bad, f"unfinished: {bad}"
    return handles


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--engine", choices=("llama", "mlp"), default="llama")
    ap.add_argument("--requests", type=int, default=16)
    args = ap.parse_args()

    from paddle_tpu.framework import monitor
    from paddle_tpu.serving import (NGramProposer, ServingFrontend,
                                    SpecDecodeConfig)

    t0 = time.time()
    rng = np.random.default_rng(0)
    warm_prompts = [rng.integers(1, 64, n).tolist() for n in (2, 5, 9, 14)]
    # repetition-leaning prompts so the n-gram proposer has something to
    # match, mixed with plain random ones
    prompts = []
    for i in range(args.requests):
        if i % 2:
            phrase = rng.integers(1, 64, int(rng.integers(2, 4))).tolist()
            p = (phrase * 5)[:int(rng.integers(6, 13))]
        else:
            p = rng.integers(1, 64, rng.integers(2, 14)).tolist()
        prompts.append((p, int(rng.integers(2, 7))))

    # ---- pass 1: plain decode ----
    fe = ServingFrontend(build_engine(args.engine))
    handles = drive(fe, warm_prompts, prompts, monitor)

    # zero recompiles after warmup: the ragged step holds ONE executable
    # across every batch composition and prompt length
    assert monitor.get("serving.decode_retraces") == 0, \
        f"decode retraced {monitor.get('serving.decode_retraces')}x"
    assert monitor.get("serving.ragged_retraces") == 0, \
        f"ragged retraced {monitor.get('serving.ragged_retraces')}x"

    # monotone metrics
    after = {k: monitor.get(k) for k in
             ("serving.requests_completed", "serving.tokens_generated",
              "serving.decode_steps")}
    for k, v in after.items():
        assert v > 0, f"{k} did not advance"
    s = fe.summary()
    assert s["serving.ttft_p50_ms"] <= s["serving.ttft_p99_ms"]

    # ---- pass 2: speculative decode, same weights + trace ----
    fe2 = ServingFrontend(
        build_engine(args.engine),
        spec=SpecDecodeConfig(NGramProposer(), num_draft_tokens=3))
    handles2 = drive(fe2, warm_prompts, prompts, monitor)

    for i, (a, b) in enumerate(zip(handles, handles2)):
        assert a.tokens == b.tokens, \
            f"req {i}: greedy parity broken: {a.tokens} != {b.tokens}"
    for c in ("serving.decode_retraces", "serving.ragged_retraces",
              "serving.verify_retraces", "serving.sample_retraces"):
        assert monitor.get(c) == 0, f"{c} retraced {monitor.get(c)}x"
    assert monitor.get("serving.spec_steps") > 0, "spec path never ran"

    print(json.dumps({
        "ok": True, "engine": args.engine, "requests": len(handles),
        "secs": round(time.time() - t0, 1),
        "tokens": after["serving.tokens_generated"],
        "decode_steps": after["serving.decode_steps"],
        "ttft_p50_ms": s["serving.ttft_p50_ms"],
        "ttft_p99_ms": s["serving.ttft_p99_ms"],
        "occupancy_avg_pct": s.get("serving.batch_occupancy_avg_pct"),
        "spec_greedy_parity": True,
        "spec_acceptance_pct": monitor.get("serving.spec_acceptance_pct"),
        "spec_tokens_per_lane_step":
            monitor.get("serving.spec_tokens_per_lane_step"),
    }))


if __name__ == "__main__":
    main()
