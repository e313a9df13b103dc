"""Crash-kill/resume smoke: prove the resilience subsystem end to end.

Driver (default mode) runs three child trainings of a tiny Llama on CPU:

1. **reference** — 20 uninterrupted steps, checkpointing every step;
2. **crashed** — same run, but at step 11 a fault injected at the
   ``ckpt.complete`` site SIGKILLs the process *mid-save* (shards on
   disk, no COMPLETE marker) — exactly a preemption during a write;
3. **resumed** — same command with ``--resume``: `latest_valid()` must
   quarantine the torn ``step_000011`` directory, restore step 10
   (params + optimizer moments + RNG, crc-verified), and finish.

Asserts: the resumed run's per-step losses are **token-for-token**
(`repr` string) identical to the reference run's for every replayed step,
the torn directory was quarantined (``QUARANTINED-step_000011``), and
``resilience.rollbacks == 0`` (resume is not a rollback). Budget: ~15 s
CPU (shared compilation cache + concurrent children; a loaded box may see
~20 s). Exit 0 on success; prints one JSON summary line.

Usage:
    python tools/crash_resume_smoke.py            # full driver
    python tools/crash_resume_smoke.py --child... # internal
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KILL_AT = 11
STEPS = 20


def child(args):
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, REPO)
    import jax

    jax.config.update("jax_platforms", "cpu")
    from paddle_tpu.framework import compile_cache

    # share compiled executables across the driver's three child
    # processes (and across runs) — the budget is dominated by
    # recompiling the same tiny train step three times
    compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.framework import monitor
    from paddle_tpu.models import llama_tiny
    from paddle_tpu.resilience import CheckpointManager, faults

    paddle.seed(0)  # deterministic init; restored RNG overrides on resume
    model = llama_tiny(vocab=32, layers=1, hidden=16, heads=2, seq=8)
    opt = paddle.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
    manager = CheckpointManager(args.ckpt, keep_last_n=4)

    start = 0
    if args.resume:
        res = manager.restore_latest(model=model, optimizer=opt)
        assert res is not None, "resume requested but no valid checkpoint"
        start = res.step + 1

    log = open(args.log, "a")
    for step in range(start, args.steps):
        rng = np.random.default_rng(1000 + step)  # per-step data seed:
        ids = paddle.to_tensor(rng.integers(1, 32, (2, 8)))  # replayable
        labels = paddle.to_tensor(rng.integers(1, 32, (2, 8)))
        loss, _ = model(ids, labels=labels)
        loss.backward()
        opt.step()
        opt.clear_grad()
        log.write(json.dumps({"step": step, "loss": repr(float(loss))})
                  + "\n")
        log.flush()
        if args.kill_at is not None and step == args.kill_at:
            # die DURING the save, after the shards but before COMPLETE:
            # the directory is torn exactly the way a real preemption
            # mid-write leaves it
            faults.inject("ckpt.complete", action="kill")
        manager.save(step, model=model, optimizer=opt)
    log.write(json.dumps({"counters": {
        k: v for k, v in monitor.get_all().items()
        if k.startswith("resilience.")}}) + "\n")
    log.close()
    return 0


def _spawn_child(ckpt, log, resume=False, kill_at=None, steps=STEPS):
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--ckpt", ckpt, "--log", log, "--steps", str(steps)]
    if resume:
        cmd.append("--resume")
    if kill_at is not None:
        cmd += ["--kill-at", str(kill_at)]
    return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)


def _run_child(ckpt, log, resume=False, kill_at=None, steps=STEPS):
    p = _spawn_child(ckpt, log, resume=resume, kill_at=kill_at, steps=steps)
    out, err = p.communicate()
    p.stdout_text, p.stderr_text = out, err
    return p


def _read_log(path):
    losses, counters = {}, {}
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            if "counters" in rec:
                counters = rec["counters"]
            else:
                losses[rec["step"]] = rec["loss"]
    return losses, counters


def driver():
    import tempfile

    t0 = time.time()
    work = tempfile.mkdtemp(prefix="crash_resume_smoke_")

    # 1. the run that gets SIGKILLed mid-save at step KILL_AT goes first:
    # it compiles the train step cold and leaves a warm compilation cache
    # (all three children share `jax_cache/` under the work dir)
    ckpt = os.path.join(work, "ckpt")
    log = os.path.join(work, "run.jsonl")
    crashed = _run_child(ckpt, log, kill_at=KILL_AT)
    assert crashed.returncode == -9, (
        f"expected SIGKILL death, got rc={crashed.returncode}:\n"
        f"{crashed.stderr_text[-2000:]}")
    torn = os.path.join(ckpt, f"step_{KILL_AT:06d}")
    assert os.path.isdir(torn) and not os.path.exists(
        os.path.join(torn, "COMPLETE")), "kill did not land mid-save"

    # 2+3 run concurrently (independent dirs, warm cache): the
    # uninterrupted reference trajectory, and the resume that must
    # quarantine the torn dir, restore step KILL_AT-1, and finish
    ref = _spawn_child(os.path.join(work, "ckpt_ref"),
                       os.path.join(work, "ref.jsonl"))
    resumed = _spawn_child(ckpt, log, resume=True)
    _, ref_err = ref.communicate()
    _, resumed_err = resumed.communicate()
    assert ref.returncode == 0, f"reference run failed:\n{ref_err[-2000:]}"
    ref_losses, _ = _read_log(os.path.join(work, "ref.jsonl"))
    assert len(ref_losses) == STEPS
    assert resumed.returncode == 0, \
        f"resume failed:\n{resumed_err[-2000:]}"
    assert os.path.isdir(os.path.join(
        ckpt, f"QUARANTINED-step_{KILL_AT:06d}")), \
        "torn checkpoint was not quarantined"
    assert not os.path.exists(torn)

    losses, counters = _read_log(log)
    assert len(losses) == STEPS, sorted(losses)
    # bitwise loss-trajectory continuity: every step, including the
    # replayed KILL_AT one, token-for-token vs the uninterrupted run
    mismatches = {s: (losses[s], ref_losses[s]) for s in range(STEPS)
                  if losses[s] != ref_losses[s]}
    assert not mismatches, f"loss trajectory diverged: {mismatches}"
    assert counters.get("resilience.rollbacks", 0) == 0, counters
    assert counters.get("resilience.quarantines", 0) == 1, counters

    print(json.dumps({
        "ok": True, "steps": STEPS, "killed_at": KILL_AT,
        "resumed_from": KILL_AT - 1,
        "replayed_steps_bitwise_equal": STEPS - KILL_AT,
        "quarantined": 1, "rollbacks": 0,
        "secs": round(time.time() - t0, 1),
    }))
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument("--ckpt")
    ap.add_argument("--log")
    ap.add_argument("--steps", type=int, default=STEPS)
    ap.add_argument("--kill-at", type=int, default=None)
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()
    return child(args) if args.child else driver()


if __name__ == "__main__":
    sys.exit(main())
