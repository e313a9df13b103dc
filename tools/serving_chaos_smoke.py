"""Serving chaos smoke: inject a deterministic fault at EVERY `serve.*`
site in sequence and assert the serving fault-tolerance contract holds
each time:

  1. every submitted request reaches a TERMINAL status (nothing lost);
  2. engine restarts stay within the watchdog budget;
  3. zero leaked KV blocks — the pool drains back to guard-only
     (`BlockCacheManager.utilization()` returns to the guard block);
  4. greedy token parity: every request the fault did NOT fail is
     bitwise identical to the fault-free reference run.

Sites driven: `serve.decode` (transient raise, NaN flag, targeted
`EngineStepError` — against both a decoding and a MID-CHUNKED-PREFILL
request, since prefill now rides the same ragged dispatch),
`serve.verify` (NaN flag on the speculative path; its transient shape
shares the decode handler and is unit-tested), `serve.sample`,
`serve.cache` — plus a persistent-fault run that exhausts the restart
budget and must fail everything TYPED rather than hang.

Prefix-cache pass (`serve.cache` with the radix cache ON): the fault
fires while blocks are SHARED (refcount > 1 across requests + the
tree). Afterwards: every request terminal, `kv_leaked_blocks()==0`
counted over unique physical blocks incl. the tree's leases, refcount
consistency (no shared block double-freed), survivor parity vs the
unfaulted cached run.

Adapter-pool pass (`serve.adapter`, ISSUE 18): the fault fires during a
multi-LoRA adapter LOAD (a lease miss mid-batch, with the pool smaller
than the working set so evictions are in flight). The faulted admission
fails typed `engine_fault:adapter`; every other adapter's request rides
through with survivor parity, and afterwards the pool's refcount books
audit clean (`AdapterPool.check_consistency()`, zero outstanding
leases) alongside the usual zero-leaked-KV contract.

Fleet pass (`fleet.step`): the same contract FLEET-WIDE — a replica is
killed mid-Poisson-burst (the armed `fleet.step` flag fires the chaos
kill on the busiest replica), and afterwards: every request terminal,
relocated + survivor GREEDY token streams bitwise equal to the unkilled
run's (committed-prefix parity: zero lost, zero duplicated tokens),
relocations within the per-request budget, and `kv_leaked_blocks()==0`
on every SURVIVOR (the dead replica's pool died with it).

Disaggregated pass (`fleet.handoff`, ISSUE 17): the same burst on a
2-prefill + 2-decode `DisaggRouter`; first an unkilled run proving
handed-off streams are bitwise the colocated fleet's, then the armed
``fleet.handoff`` flag kills a PREFILL worker mid-handoff — every
request still terminal (zero lost), zero leaked blocks on every
survivor, and every finished stream still bitwise the colocated
reference's.

All injection is counted-call arithmetic (`resilience.faults`): no
clocks, no randomness, no sleeps. Tier-1-safe: MLP engine, < 15 s CPU.

Usage:
    python tools/serving_chaos_smoke.py

Exit code 0 on success; prints one JSON line per scenario plus a final
summary line.
"""
from __future__ import annotations

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# every scenario builds fresh engines (and the watchdog rebuilds them
# mid-run): share one persistent compilation cache so identical-shape
# traces compile once, keeping the whole smoke under its CI budget
from paddle_tpu.framework import compile_cache  # noqa: E402

compile_cache.configure()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

import numpy as np  # noqa: E402

VOCAB = 64
MAX_RESTARTS = 2


def make_engine():
    from paddle_tpu.serving import MLPLMEngine

    return MLPLMEngine(vocab_size=VOCAB, hidden=16, max_batch_size=4,
                       num_blocks=48, block_size=4, max_blocks_per_seq=8)


def trace():
    """Fixed request mix: repetition-leaning prompts (so the speculative
    pass actually drafts) plus plain random ones."""
    rng = np.random.default_rng(0)
    out = []
    for i in range(8):
        if i % 2:
            phrase = rng.integers(1, VOCAB, int(rng.integers(2, 4))).tolist()
            out.append((phrase * 5)[:int(rng.integers(6, 13))])
        else:
            out.append(rng.integers(1, VOCAB, rng.integers(2, 10)).tolist())
    return out


def run_once(arm=None, spec=False, watchdog=True):
    """Serve the fixed trace; `arm(handles)` arms the injection after
    submission (so it can target a live request id). Returns the
    frontend and its handles."""
    from paddle_tpu.serving import (NGramProposer, ServingFrontend,
                                    ServingMetrics, SpecDecodeConfig,
                                    WatchdogConfig)

    ServingMetrics.reset_monitor()
    fe = ServingFrontend(
        make_engine(),
        spec=SpecDecodeConfig(NGramProposer(), num_draft_tokens=3)
        if spec else None,
        watchdog=WatchdogConfig(step_retries=2, max_restarts=MAX_RESTARTS)
        if watchdog else None,
        engine_factory=make_engine if watchdog else None,
        stall_after=256)
    handles = [fe.submit(p, max_new_tokens=6) for p in trace()]
    if arm is not None:
        arm(handles)
    fe.run_until_idle(max_steps=4000)
    return fe, handles


def check_contract(name, fe, handles, reference, expect_failed=None):
    """The four chaos assertions; returns the per-scenario report."""
    from paddle_tpu.framework import monitor
    from paddle_tpu.serving import RequestStatus

    # 1. nothing lost: every request terminal
    non_terminal = [h.request_id for h in handles if not h.finished]
    assert not non_terminal, f"{name}: non-terminal requests {non_terminal}"
    # 2. restarts within budget
    restarts = monitor.get("serving.engine_restarts")
    assert restarts <= MAX_RESTARTS, f"{name}: {restarts} restarts"
    # 3. zero leaked KV blocks: pool back to guard-only
    leaked = fe.scheduler.kv_leaked_blocks()
    assert leaked == 0, f"{name}: {leaked} leaked blocks"
    mgr = fe.scheduler.engine.manager
    assert mgr.free_blocks == mgr.num_blocks - 1, \
        f"{name}: {mgr.num_blocks - mgr.free_blocks} blocks still leased"
    # 4. greedy parity for every request the fault did not touch
    failed = [h for h in handles if h.status is RequestStatus.FAILED]
    mismatch = [i for i, (h, ref) in enumerate(zip(handles, reference))
                if h.status is RequestStatus.FINISHED and h.tokens != ref]
    assert not mismatch, f"{name}: survivor token mismatch at {mismatch}"
    if expect_failed is not None:
        got = sorted(h.finish_reason for h in failed)
        assert got == sorted(expect_failed), \
            f"{name}: failed reasons {got} != {expect_failed}"
    report = {
        "scenario": name,
        "finished": sum(h.status is RequestStatus.FINISHED for h in handles),
        "failed": len(failed),
        "failed_reasons": sorted({h.finish_reason for h in failed}),
        "restarts": restarts,
        "isolated_faults": monitor.get("serving.isolated_faults"),
        "step_faults": monitor.get("serving.step_faults"),
        "leaked_blocks": leaked,
        "survivor_parity": True,
    }
    print(json.dumps(report))
    return report


def make_quant_engine():
    """Quantized twin of `make_engine`: int8 KV pool + int8 weight-only
    gemms (PR 14, serving/quant.py)."""
    from paddle_tpu.serving import MLPLMEngine, quantize_engine

    return quantize_engine(
        MLPLMEngine(vocab_size=VOCAB, hidden=16, max_batch_size=4,
                    num_blocks=48, block_size=4, max_blocks_per_seq=8,
                    kv_bits=8), wbits=8)


def quant_run(arm=None):
    from paddle_tpu.serving import (ServingFrontend, ServingMetrics,
                                    WatchdogConfig)

    ServingMetrics.reset_monitor()
    fe = ServingFrontend(
        make_quant_engine(),
        watchdog=WatchdogConfig(step_retries=2, max_restarts=MAX_RESTARTS),
        engine_factory=make_quant_engine, stall_after=256)
    handles = [fe.submit(p, max_new_tokens=6) for p in trace()]
    if arm is not None:
        arm(handles)
    fe.run_until_idle(max_steps=4000)
    return fe, handles


def quant_chaos():
    """Quantized-pool pass: the `serve.cache` fault fires against an
    int8 KV pool (per-slot scale planes riding every block). The
    terminal-status and leak contracts must hold bit-for-bit like the
    full-precision pool's — the scale plane is part of the block, so a
    leaked or double-freed block would show up identically — and the
    fragmentation telemetry must report the quantized byte geometry
    (kv_bits/bytes_per_block, the PR 14 capacity-audit fields)."""
    from paddle_tpu.framework import monitor
    from paddle_tpu.resilience import faults
    from paddle_tpu.serving import RequestStatus

    faults.clear()
    _, ref_h = quant_run()
    assert all(h.status is RequestStatus.FINISHED for h in ref_h), \
        "quantized fault-free reference did not finish"
    reference = [h.tokens for h in ref_h]

    faults.clear()
    fe, hs = quant_run(
        arm=lambda _h: faults.inject("serve.cache", after_n=6, times=1))
    faults.clear()
    report = check_contract("serve.cache:int8_pool", fe, hs, reference,
                            expect_failed=["engine_fault:cache"])
    frag = fe.scheduler.engine.manager.fragmentation()
    assert frag["kv_bits"] == 8, frag
    assert frag["bytes_per_block"] and frag["pool_bytes"], frag
    assert monitor.get("serving.quant.kv_bits") == 8
    assert monitor.get("serving.quant.wbits") == 8
    report["kv_bits"] = frag["kv_bits"]
    report["bytes_per_block"] = frag["bytes_per_block"]
    return report


def make_lora_engine():
    """Multi-LoRA twin of `make_engine` (ISSUE 18): a paged adapter pool
    DELIBERATELY smaller than the working set (3 slots, 6 adapters) so
    the faulted run exercises the load/evict path mid-batch, not just
    resident hits. Registration is seed-deterministic, so the watchdog's
    rebuilt engine carries identical adapter weights."""
    from paddle_tpu.serving import MLPLMEngine, attach_adapters
    from paddle_tpu.serving.lora import random_adapter

    eng = attach_adapters(
        MLPLMEngine(vocab_size=VOCAB, hidden=16, max_batch_size=4,
                    num_blocks=48, block_size=4, max_blocks_per_seq=8),
        pool_slots=3, rank_buckets=(2, 4))
    for i in range(6):
        eng.adapter_pool.register(
            f"ad{i}", random_adapter(eng, rank=2 + 2 * (i % 2), seed=i))
    return eng


def lora_run(arm=None):
    from paddle_tpu.serving import (ServingFrontend, ServingMetrics,
                                    WatchdogConfig)

    ServingMetrics.reset_monitor()
    fe = ServingFrontend(
        make_lora_engine(),
        watchdog=WatchdogConfig(step_retries=2, max_restarts=MAX_RESTARTS),
        engine_factory=make_lora_engine, stall_after=256)
    handles = [fe.submit(p, max_new_tokens=6, adapter=f"ad{i % 6}")
               for i, p in enumerate(trace())]
    if arm is not None:
        arm(handles)
    fe.run_until_idle(max_steps=4000)
    return fe, handles


def lora_chaos():
    """Adapter-pool pass: the `serve.adapter` fault fires during an
    adapter LOAD (a lease miss — upload/evict in flight) mid-batch. The
    faulted admission must fail typed `engine_fault:adapter` while every
    other request rides through; afterwards the pool's refcount books
    must audit clean (zero leases, slot-map invertible, free list
    disjoint) on top of the usual terminal/leak/parity contract."""
    from paddle_tpu.framework import monitor
    from paddle_tpu.resilience import faults
    from paddle_tpu.serving import RequestStatus

    faults.clear()
    _, ref_h = lora_run()
    assert all(h.status is RequestStatus.FINISHED for h in ref_h), \
        "multi-LoRA fault-free reference did not finish"
    assert monitor.get("serving.lora.evictions") > 0, \
        "pool (3 slots) vs working set (6 adapters) produced no evictions"
    reference = [h.tokens for h in ref_h]

    faults.clear()
    fe, hs = lora_run(
        arm=lambda _h: faults.inject("serve.adapter", after_n=3, times=1))
    faults.clear()
    report = check_contract("serve.adapter:pool", fe, hs, reference,
                            expect_failed=["engine_fault:adapter"])
    pool = fe.scheduler.engine.adapter_pool
    pool.check_consistency()
    assert pool.leases() == 0, f"adapter leases leaked: {pool.leases()}"
    stats = pool.stats()
    report["adapter_pool"] = {"slots": stats["pool_slots"],
                              "resident": stats["resident_adapters"],
                              "evictions": monitor.get(
                                  "serving.lora.evictions"),
                              "miss_loads": monitor.get(
                                  "serving.lora.miss_loads")}
    return report


def fleet_trace():
    """Deterministic Poisson-ish burst: step index -> requests arriving
    then (seeded rng; no clocks, no sleeps)."""
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, VOCAB, int(rng.integers(3, 10))).tolist()
               for _ in range(18)]
    arrivals = []
    i = 0
    step = 0
    while i < len(prompts):
        k = int(rng.poisson(1.6))
        for _ in range(min(k, len(prompts) - i)):
            arrivals.append((step, prompts[i]))
            i += 1
        step += 1
    return arrivals


def fleet_run(kill_at_step=None, relocation_budget=2):
    """Serve the deterministic burst on a 3-replica fleet, optionally
    arming `fleet.step` to chaos-kill the busiest replica mid-burst.
    Returns (router, handles)."""
    from paddle_tpu.resilience import faults
    from paddle_tpu.serving import (FleetRouter, ServingMetrics,
                                    WatchdogConfig)

    ServingMetrics.reset_monitor()
    from paddle_tpu.framework import monitor

    monitor.reset_prefix("fleet.")
    router = FleetRouter(
        make_engine, num_replicas=3,
        relocation_budget=relocation_budget,
        frontend_kwargs=dict(watchdog=WatchdogConfig(
            step_retries=2, max_restarts=MAX_RESTARTS)))
    if kill_at_step is not None:
        faults.inject("fleet.step", after_n=kill_at_step, times=1,
                      action="flag")
    handles = []
    arrivals = fleet_trace()
    i = 0
    step = 0
    while i < len(arrivals) or not router.idle:
        while i < len(arrivals) and arrivals[i][0] <= step:
            handles.append(router.submit(arrivals[i][1],
                                         max_new_tokens=6))
            i += 1
        router.step()
        step += 1
        assert step < 4000, "fleet burst never drained"
    faults.clear()
    return router, handles


def fleet_chaos(reference_tokens):
    """The fleet-wide chaos scenario: kill a replica mid-burst, assert
    the fleet-wide contract."""
    from paddle_tpu.framework import monitor
    from paddle_tpu.serving import RequestStatus

    router, handles = fleet_run(kill_at_step=4)
    try:
        dead = [r for r in router.replicas if not r.alive]
        survivors = [r for r in router.replicas if r.alive]
        assert len(dead) == 1 and dead[0].death_reason == "chaos_kill", \
            f"expected exactly one chaos kill, got {dead}"
        # 1. nothing lost fleet-wide
        non_terminal = [h.request_id for h in handles if not h.finished]
        assert not non_terminal, f"non-terminal after drain {non_terminal}"
        # 2. greedy token parity vs the unkilled run — for EVERY finished
        # request, including the relocated ones (committed-prefix parity:
        # prefix carried + survivor continuation == uninterrupted stream)
        mismatch = [i for i, (h, ref) in
                    enumerate(zip(handles, reference_tokens))
                    if h.status is RequestStatus.FINISHED
                    and h.tokens != ref]
        assert not mismatch, f"token parity broke at {mismatch}"
        relocated = [h for h in handles if h.num_relocations > 0]
        assert relocated, "the kill relocated nothing — it missed " \
            "every in-flight request (tune kill_at_step)"
        # 3. relocation budget respected
        over = [h.request_id for h in handles
                if h.num_relocations > router.relocation_budget]
        assert not over, f"relocation budget exceeded {over}"
        # 4. zero leaked KV blocks on every survivor
        for rep in survivors:
            leaked = rep.scheduler.kv_leaked_blocks()
            assert leaked == 0, f"{rep.replica_id}: {leaked} leaked"
        # replica-level restarts stayed within each watchdog's budget
        restarts = monitor.get("serving.engine_restarts")
        assert restarts <= MAX_RESTARTS * 3, f"{restarts} restarts"
        report = {
            "scenario": "fleet.step:chaos_kill",
            "requests": len(handles),
            "finished": sum(h.status is RequestStatus.FINISHED
                            for h in handles),
            "killed": dead[0].replica_id,
            "relocated": len(relocated),
            "relocations": monitor.get("fleet.relocations"),
            "relocated_tokens": monitor.get("fleet.relocated_tokens"),
            "survivor_parity": True,
            "leaked_blocks": 0,
        }
        print(json.dumps(report))
        return report
    finally:
        router.close()


def disagg_run(kill_handoff_at=None, relocation_budget=2):
    """Serve the same deterministic burst on a 2-prefill + 2-decode
    disaggregated fleet (`serving/disagg.py`), optionally arming
    ``fleet.handoff`` with ``action="flag"`` so the k-th handoff kills
    its PREFILL worker mid-migration. Returns (router, handles)."""
    from paddle_tpu.framework import monitor
    from paddle_tpu.resilience import faults
    from paddle_tpu.serving import (DisaggRouter, ServingMetrics,
                                    WatchdogConfig)

    ServingMetrics.reset_monitor()
    monitor.reset_prefix("fleet.")
    router = DisaggRouter(
        make_engine, num_prefill=2, num_decode=2,
        relocation_budget=relocation_budget,
        frontend_kwargs=dict(watchdog=WatchdogConfig(
            step_retries=2, max_restarts=MAX_RESTARTS)))
    if kill_handoff_at is not None:
        faults.inject("fleet.handoff", after_n=kill_handoff_at, times=1,
                      action="flag")
    handles = []
    arrivals = fleet_trace()
    i = 0
    step = 0
    while i < len(arrivals) or not router.idle:
        while i < len(arrivals) and arrivals[i][0] <= step:
            handles.append(router.submit(arrivals[i][1],
                                         max_new_tokens=6))
            i += 1
        router.step()
        step += 1
        assert step < 4000, "disagg burst never drained"
    faults.clear()
    return router, handles


def disagg_chaos(reference_tokens):
    """Disaggregated pass (ISSUE 17): kill a prefill worker MID-HANDOFF
    (the armed ``fleet.handoff`` flag fires between extraction and the
    decode-tier import). Contract: every request terminal (zero lost),
    zero leaked blocks on every survivor, and every finished greedy
    stream — handed-off, fold-relocated, and untouched alike — bitwise
    equal to the unkilled colocated run's."""
    from paddle_tpu.framework import monitor
    from paddle_tpu.serving import RequestStatus

    # unkilled disagg reference first: handoffs happen, streams must
    # already match the colocated fleet reference bitwise
    router, handles = disagg_run()
    try:
        assert all(h.status is RequestStatus.FINISHED for h in handles)
        mismatch = [i for i, (h, ref) in
                    enumerate(zip(handles, reference_tokens))
                    if h.tokens != ref]
        assert not mismatch, \
            f"disagg-vs-colocated parity broke at {mismatch}"
        handoffs = monitor.get("fleet.handoffs")
        assert handoffs > 0, "no handoffs — the tiers never streamed"
        assert monitor.get("serving.handoff.count") == handoffs
        assert monitor.get("serving.handoff.bytes") > 0
    finally:
        router.close()

    router, handles = disagg_run(kill_handoff_at=2)
    try:
        dead = [r for r in router.replicas if not r.alive]
        survivors = [r for r in router.replicas if r.alive]
        assert len(dead) == 1 and dead[0].role == "prefill" \
            and dead[0].death_reason == "handoff_chaos_kill", \
            f"expected one prefill worker dead mid-handoff, got {dead}"
        # 1. nothing lost: every request terminal
        non_terminal = [h.request_id for h in handles if not h.finished]
        assert not non_terminal, f"non-terminal after kill {non_terminal}"
        # 2. greedy parity vs the unkilled colocated run for EVERY
        # finished request — handed-off and fold-relocated alike
        mismatch = [i for i, (h, ref) in
                    enumerate(zip(handles, reference_tokens))
                    if h.status is RequestStatus.FINISHED
                    and h.tokens != ref]
        assert not mismatch, f"handoff-kill parity broke at {mismatch}"
        relocated = [h for h in handles if h.num_relocations > 0]
        assert relocated, "the mid-handoff kill relocated nothing — " \
            "tune kill_handoff_at"
        # 3. zero leaked KV blocks on every survivor (the dead prefill
        # worker's pool died with it; targets never allocated for the
        # interrupted handoff)
        for rep in survivors:
            leaked = rep.scheduler.kv_leaked_blocks()
            assert leaked == 0, f"{rep.replica_id}: {leaked} leaked"
        report = {
            "scenario": "fleet.handoff:prefill_kill",
            "requests": len(handles),
            "finished": sum(h.status is RequestStatus.FINISHED
                            for h in handles),
            "killed": dead[0].replica_id,
            "killed_role": dead[0].role,
            "handoffs": monitor.get("fleet.handoffs"),
            "handoff_fallbacks": monitor.get("fleet.handoff_fallbacks"),
            "relocated": len(relocated),
            "relocations_shipped":
                monitor.get("fleet.relocations_shipped"),
            "survivor_parity": True,
            "leaked_blocks": 0,
        }
        print(json.dumps(report))
        return report
    finally:
        router.close()


def prefix_trace():
    """Shared-prefix mix: 6 of 8 prompts carry one 12-token system
    prefix (3 full blocks at block_size 4) plus a unique suffix — once
    the first finisher publishes, later admissions lease shared blocks,
    so the injected cache fault lands while refcounts are > 1."""
    rng = np.random.default_rng(3)
    shared = rng.integers(1, VOCAB, 12).tolist()
    out = []
    for i in range(8):
        if i % 4 == 3:
            out.append(rng.integers(1, VOCAB, 7).tolist())
        else:
            out.append(shared + rng.integers(1, VOCAB, 3).tolist())
    return out


def prefix_run(arm=None):
    from paddle_tpu.serving import (ServingFrontend, ServingMetrics,
                                    WatchdogConfig)

    ServingMetrics.reset_monitor()
    fe = ServingFrontend(
        make_engine(), prefix_cache=True,
        watchdog=WatchdogConfig(step_retries=2, max_restarts=MAX_RESTARTS),
        engine_factory=make_engine, stall_after=256)
    handles = [fe.submit(p, max_new_tokens=6) for p in prefix_trace()]
    if arm is not None:
        arm(handles)
    fe.run_until_idle(max_steps=4000)
    return fe, handles


def prefix_chaos():
    """Prefix-cache pass: a `serve.cache` fault fires while blocks are
    SHARED (refcount > 1). Contract: every request terminal, zero
    leaked blocks (unique-counted across sequences AND the radix tree),
    no shared block double-freed (refcount consistency audit incl. the
    tree's leases), survivors bitwise equal to the unfaulted cached
    run."""
    from paddle_tpu.framework import monitor
    from paddle_tpu.resilience import faults
    from paddle_tpu.serving import RequestStatus

    faults.clear()
    ref_fe, ref_h = prefix_run()
    assert all(h.status is RequestStatus.FINISHED for h in ref_h), ref_h
    ref_tree = ref_fe.scheduler.prefix_cache
    assert ref_tree.hits >= 2, \
        f"trace never shared blocks (hits {ref_tree.hits}) — the fault " \
        f"would not land on shared state"
    reference = [h.tokens for h in ref_h]

    faults.clear()
    # after_n=16: past admission allocates, into the mid-run append path
    # where shared leases + COW live
    fe, hs = prefix_run(arm=lambda _h: faults.inject(
        "serve.cache", after_n=16, times=1))
    faults.clear()
    non_terminal = [h.request_id for h in hs if not h.finished]
    assert not non_terminal, f"prefix: non-terminal {non_terminal}"
    sched = fe.scheduler
    tree = sched.prefix_cache
    leaked = sched.kv_leaked_blocks()
    assert leaked == 0, f"prefix: {leaked} leaked blocks"
    mgr = sched.engine.manager
    # no double-free: refcounts exactly match table + tree leases, the
    # free list is duplicate-free, every block accounted once
    mgr.check_consistency(external=tree.block_ref_counts())
    assert mgr.free_blocks == mgr.num_blocks - 1 - tree.num_nodes, \
        f"prefix: pool holds {mgr.num_blocks - mgr.free_blocks} != " \
        f"guard + {tree.num_nodes} tree nodes"
    failed = [h for h in hs if h.status is RequestStatus.FAILED]
    mismatch = [i for i, (h, ref) in enumerate(zip(hs, reference))
                if h.status is RequestStatus.FINISHED and h.tokens != ref]
    assert not mismatch, f"prefix: survivor mismatch at {mismatch}"
    report = {
        "scenario": "serve.cache:prefix_shared",
        "finished": sum(h.status is RequestStatus.FINISHED for h in hs),
        "failed": len(failed),
        "tree_nodes": tree.num_nodes,
        "prefix_hits": tree.hits,
        "cow_copies": mgr.cow_copies,
        "leaked_blocks": leaked,
        "double_free": False,
        "survivor_parity": True,
        "restarts": monitor.get("serving.engine_restarts"),
    }
    print(json.dumps(report))
    return report


def main():
    from paddle_tpu.resilience import faults
    from paddle_tpu.serving import EngineStepError, RequestStatus

    t0 = time.time()
    reports = []

    # fault-free references (plain and speculative decode agree greedily,
    # but run both so each faulted pass compares against its own shape)
    _, ref_h = run_once()
    reference = [h.tokens for h in ref_h]
    assert all(h.status is RequestStatus.FINISHED for h in ref_h)
    _, ref_spec_h = run_once(spec=True)
    assert [h.tokens for h in ref_spec_h] == reference, \
        "speculative reference diverged from plain decode"

    scenarios = [
        ("serve.decode:prefill_chunk_targeted",
         # fires on the FIRST ragged dispatch, while hs[0] is still
         # prefilling: a fault attributed to a mid-prefill lane fails
         # only it, before its first token
         lambda hs: faults.inject(
             "serve.decode", after_n=0, times=1,
             exc=EngineStepError("decode", seq_ids=[hs[0].request_id])),
         dict(expect_failed=["engine_fault:decode"])),
        ("serve.decode:transient",
         lambda hs: faults.inject("serve.decode", after_n=2, times=1),
         dict(expect_failed=[])),
        ("serve.decode:nan_flag",
         lambda hs: faults.inject("serve.decode", after_n=1, times=1,
                                  action="flag"),
         dict(expect_failed=["nan_logits"])),
        ("serve.decode:targeted",
         lambda hs: faults.inject(
             "serve.decode", after_n=1, times=1,
             exc=EngineStepError("decode", seq_ids=[hs[3].request_id])),
         dict(expect_failed=["engine_fault:decode"])),
        ("serve.verify:nan_flag",
         lambda hs: faults.inject("serve.verify", after_n=1, times=1,
                                  action="flag"),
         dict(spec=True, expect_failed=["nan_logits"])),
        ("serve.sample:raise",
         lambda hs: faults.inject("serve.sample", after_n=4, times=1),
         dict()),   # admission- vs decode-phase hit differ in outcome;
                    # the contract assertions cover both
        ("serve.cache:raise",
         lambda hs: faults.inject("serve.cache", after_n=6, times=1),
         dict(expect_failed=["engine_fault:cache"])),
    ]
    for name, arm, kw in scenarios:
        faults.clear()
        spec = kw.pop("spec", False)
        expect_failed = kw.pop("expect_failed", None)
        fe, hs = run_once(arm=arm, spec=spec)
        faults.clear()
        reports.append(check_contract(name, fe, hs, reference,
                                      expect_failed=expect_failed))

    # persistent fault: the watchdog must exhaust its budget and fail
    # EVERYTHING typed — never hang, never leak
    faults.clear()
    fe, hs = run_once(
        arm=lambda _h: faults.inject("serve.decode", times=None))
    faults.clear()
    assert all(h.finished for h in hs), "persistent-fault run hung"
    assert all(h.status is RequestStatus.FAILED for h in hs)
    assert all(h.finish_reason.startswith("engine_unrecoverable")
               for h in hs)
    from paddle_tpu.framework import monitor
    assert monitor.get("serving.engine_restarts") == MAX_RESTARTS
    assert fe.scheduler.kv_leaked_blocks() == 0
    reports.append({"scenario": "serve.decode:persistent",
                    "failed": len(hs),
                    "restarts": monitor.get("serving.engine_restarts"),
                    "typed": True})
    print(json.dumps(reports[-1]))

    # prefix-cache pass: serve.cache fault while blocks are shared
    reports.append(prefix_chaos())

    # quantized-pool pass: serve.cache fault against int8 KV + scale
    # planes (PR 14) — same zero-leak / terminal-status contract
    reports.append(quant_chaos())

    # adapter-pool pass (ISSUE 18): serve.adapter fault during an
    # adapter load/evict mid-batch — typed failure, clean refcount books
    reports.append(lora_chaos())

    # fleet-wide pass: unkilled reference, then the mid-burst replica kill
    faults.clear()
    ref_router, ref_handles = fleet_run()
    try:
        assert all(h.status is RequestStatus.FINISHED for h in ref_handles)
        assert all(h.num_relocations == 0 for h in ref_handles)
        fleet_reference = [h.tokens for h in ref_handles]
    finally:
        ref_router.close()
    reports.append(fleet_chaos(fleet_reference))

    # disaggregated pass (ISSUE 17): prefill worker killed mid-handoff
    faults.clear()
    reports.append(disagg_chaos(fleet_reference))

    print(json.dumps({
        "ok": True,
        "scenarios": len(reports),
        "secs": round(time.time() - t0, 1),
        "contract": "all requests terminal, restarts <= budget, "
                    "0 leaked blocks, survivor greedy parity, "
                    "prefix cache: shared-block fault -> no double-free, "
                    "int8 KV pool: cache fault -> zero leaks, quantized "
                    "byte geometry in telemetry, "
                    "adapter pool: load fault -> typed failure, "
                    "refcount books audit clean, "
                    "fleet: replica kill -> relocation parity, "
                    "relocations <= budget, survivors leak-free, "
                    "disagg: prefill kill mid-handoff -> zero lost, "
                    "zero leaked, handed-off streams bitwise colocated",
    }))


if __name__ == "__main__":
    main()
