"""Pipeline-parallel micro-batch schedulers.

Analog of `fleet/meta_parallel/pipeline_parallel.py` (`PipelineParallel:245`
1F1B, `PipelineParallelWithInterleave:1161` VPP, `...FthenB:2018`) and the
static zero-bubble schedules
(`distributed/passes/pipeline_scheduler_pass/pipeline_zero_bubble.py`).

Two faces, one API:

1. **Eager scheduler** (`train_batch`): a real pipelined executor.
   `build_schedule` produces the slot-by-slot (stage, micro, F/B) work order
   for FThenB / 1F1B / VPP-interleave — the same orders the reference's
   schedulers emit — and the engine executes it: each stage's params are
   `device_put` onto that stage's `pp`-coordinate sub-mesh, boundary
   activations are detached and transferred to the next stage's devices (the
   ICI p2p, reference `pp_utils/p2p_communication.py:51`), and each B step is
   a per-stage `paddle.grad` VJP seeded with the upstream boundary cotangent.
   Because XLA dispatch is async, F(s, m) on stage s's device overlaps
   F(s+1, m-1) on stage s+1's — true pipelining under a single controller.
   1F1B frees each micro's activations right after its backward; the engine
   tracks live-activation counts so the schedules' defining memory profiles
   are observable (`peak_live_activations`).

2. **Compiled path** (`scan_pipeline` / `pipeline_train_step`): the
   TPU-native form — all stages run as ONE jitted program, micro-batches
   flow through a `lax.scan` whose carry `ppermute`s stage outputs around
   the `pp` mesh axis (SURVEY.md §7.3 hard-part 2). `pipeline_train_step`
   runs loss + backward inside the program (`jax.value_and_grad`
   differentiates through the ppermute ring); schedule choice maps to the
   memory policy (FThenB = save-everything, 1F1B = per-stage remat) and VPP
   to chunked scans. Zero-bubble variants become scan-schedule layouts
   instead of hand-written interceptor graphs (`fleet_executor/carrier.h:50`
   has no role on TPU).
"""
from __future__ import annotations

from typing import Callable, List, Optional, Tuple

import numpy as np

from ....core.tensor import Tensor
from ..base.topology import get_hybrid_communicate_group
from .pp_layers import PipelineLayer

__all__ = ["PipelineParallel", "scan_pipeline", "pipeline_train_step",
           "build_schedule", "bubble_fraction", "analytic_bubble_fraction",
           "pipeline_layer_to_stage_fn"]


# ---------------------------------------------------------------------------
# schedule construction (shared by the eager engine and the tests)
# ---------------------------------------------------------------------------

def build_schedule(schedule: str, n_stages: int, n_micro: int,
                   n_chunks: int = 1) -> List[List[tuple]]:
    """Slot-by-slot work order for an S-stage pipeline over M micro-batches.

    Returns a list of time slots; each slot is a list of work items
    ``(chunk, stage, micro, op)`` with op in {"F", "B"}; virtual stage
    ``chunk*S + stage`` runs on device ``stage``. Items in one slot run
    concurrently (different devices). Dependencies honoured:
    F(vs, m) needs F(vs-1, m); B(vs, m) needs F(vs, m) and B(vs+1, m);
    per virtual stage, micro-batches proceed in order.

    The schedule string picks the per-device priority — the exact mechanism
    that distinguishes the reference's schedulers
    (`pipeline_parallel.py:245,1161,2018`):
    - FThenB: forwards before backwards -> all M activations live at peak.
    - 1F1B / VPP: backwards as soon as ready -> peak live activations per
      stage is bounded by the pipeline depth, not M.
    - ZBH1 / ZBVPP (zero-bubble, reference
      `pipeline_scheduler_pass/pipeline_zero_bubble.py:61,151`): each B is
      SPLIT into "BX" (input/dgrad — on the critical path, scheduled like
      1F1B's B) and "BW" (weight grad — no cross-stage deps, fills the
      warmup/cooldown bubbles). Work items then use ops {"F","BX","BW"}
      and the measured bubble drops below 1F1B's.
    """
    sched = schedule.upper().replace("-", "")
    S, M, V = int(n_stages), int(n_micro), max(1, int(n_chunks))
    n_virt = S * V
    zero_bubble = sched in ("ZBH1", "ZB", "ZBVPP")
    prefer_b = sched not in ("FTHENB",)
    # per-virtual-stage FIFO queues (micro order)
    f_q = {vs: list(range(M)) for vs in range(n_virt)}
    b_q = {vs: list(range(M)) for vs in range(n_virt)}
    w_q = {vs: list(range(M)) for vs in range(n_virt)} if zero_bubble else {}
    fwd_done, bwd_done = set(), set()
    live = {d: 0 for d in range(S)}  # in-flight micros (F issued, BX not yet)
    slots: List[List[tuple]] = []
    b_op = "BX" if zero_bubble else "B"
    total = (3 if zero_bubble else 2) * n_virt * M
    done = 0
    while done < total:
        slot = []
        for d in range(S):
            # 1F1B warmup bound: stage d keeps at most S-d micros in flight
            # (the reference's warmup = S-d-1 forwards then strict 1F1B);
            # interleave keeps a full S-wide window per extra chunk
            # (Megatron interleaved warmup spans the chunk windows).
            cap = (S - d) + S * (V - 1) if prefer_b else M * V
            cands = []
            for c in range(V):
                vs = c * S + d
                if f_q[vs] and live[d] < cap:
                    m = f_q[vs][0]
                    if vs == 0 or (vs - 1, m) in fwd_done:
                        cands.append(("F", vs, c, m))
                if b_q[vs]:
                    m = b_q[vs][0]
                    if (vs, m) in fwd_done and (
                            vs == n_virt - 1 or (vs + 1, m) in bwd_done):
                        cands.append((b_op, vs, c, m))
                if zero_bubble and w_q[vs]:
                    m = w_q[vs][0]
                    if (vs, m) in bwd_done:
                        cands.append(("BW", vs, c, m))
            if not cands:
                continue
            # priority: dgrad first (critical path), then forwards, weight
            # grads last — they only fill otherwise-idle slots
            if prefer_b:
                picks = ([x for x in cands if x[0] == b_op]
                         or [x for x in cands if x[0] == "F"] or cands)
            else:
                picks = [x for x in cands if x[0] == "F"] or cands
            op, vs, c, m = min(picks, key=lambda x: (x[3], x[2]))
            slot.append((c, d, m, op))
        if not slot:
            raise RuntimeError("pipeline schedule deadlock (bug)")
        # commit the slot's effects after selection so in-slot choices only
        # see state from previous slots (items run concurrently)
        for c, d, m, op in slot:
            vs = c * S + d
            if op == "F":
                f_q[vs].pop(0)
                fwd_done.add((vs, m))
                live[d] += 1
            elif op == "BW":
                w_q[vs].pop(0)
            else:
                b_q[vs].pop(0)
                bwd_done.add((vs, m))
                live[d] -= 1
            done += 1
        slots.append(slot)
    return slots


def bubble_fraction(slots: List[List[tuple]], n_stages: int) -> float:
    """Measured pipeline bubble: idle device-slots / total device-slots."""
    work = sum(len(s) for s in slots)
    total = n_stages * len(slots)
    return 1.0 - work / total


def analytic_bubble_fraction(schedule: str, n_stages: int, n_micro: int,
                             n_chunks: int = 1) -> float:
    """Closed-form bubble fraction (Megatron accounting): (S-1)/(V*M + S-1)
    for VPP-interleave, (S-1)/(M + S-1) for FThenB/1F1B."""
    S, M, V = n_stages, n_micro, max(1, n_chunks)
    if schedule.upper().replace("-", "") in ("VPP", "INTERLEAVE"):
        return (S - 1) / (V * M + S - 1)
    return (S - 1) / (M + S - 1)


# ---------------------------------------------------------------------------
# the eager pipelined executor
# ---------------------------------------------------------------------------

class PipelineParallel:
    """Pipelined train/eval over a `PipelineLayer` (reference
    `PipelineParallel:245`). See the module docstring for the execution
    model; `schedule_log` and `peak_live_activations` expose what ran."""

    def __init__(self, layers, hcg=None, strategy=None):
        if not isinstance(layers, PipelineLayer):
            raise TypeError("PipelineParallel needs a PipelineLayer")
        self._layers = layers
        self._hcg = hcg or get_hybrid_communicate_group()
        self._strategy = strategy
        cfg = getattr(strategy, "pipeline_configs", None) or {}
        self.accumulate_steps = int(cfg.get("accumulate_steps", 1))
        self.micro_batch_size = int(cfg.get("micro_batch_size", 1))
        self.schedule = cfg.get("schedule_mode", "1F1B")
        self.n_chunks = int(cfg.get("num_virtual_pipeline_stages", 1) or 1)
        self.total_loss = None
        self.schedule_log: List[tuple] = []
        self.peak_live_activations: dict = {}
        self._segments = self._build_segments()
        self._params_of_segment = [self._collect_segment_params(vs)
                                   for vs in range(len(self._segments))]
        self._stage_shardings = self._place_stages()

    # -- placement -----------------------------------------------------------
    def _build_segments(self):
        """Partition the layer list into S*V virtual-stage segments."""
        S = self._layers.num_stages
        V = self.n_chunks
        if V == 1:
            return [self._layers.stage_layers(s) for s in range(S)]
        fns = self._layers.run_function
        n = len(fns)
        n_virt = S * V
        per = [n // n_virt + (1 if i < n % n_virt else 0)
               for i in range(n_virt)]
        bounds = [0]
        for p in per:
            bounds.append(bounds[-1] + p)
        return [fns[bounds[i]:bounds[i + 1]] for i in range(n_virt)]

    def _place_stages(self):
        """device_put each stage's params onto its pp-coordinate sub-mesh.

        The single-controller analog of each rank holding only its stage:
        stage s's weights live on the devices at pp==s; boundary activations
        move between the sub-meshes (ICI). Returns per-device shardings (or
        None when there's no multi-device pp axis to place on)."""
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        S = self._layers.num_stages
        if self._hcg is None or S <= 1:
            return None
        mesh = self._hcg.get_hybrid_mesh().to_jax_mesh()
        if "pp" not in mesh.axis_names or mesh.shape["pp"] != S:
            return None
        if mesh.devices.size < S:
            return None
        pp_axis = list(mesh.axis_names).index("pp")
        rest_names = [n for n in mesh.axis_names if n != "pp"]
        shardings = []
        for s in range(S):
            sub = np.take(mesh.devices, s, axis=pp_axis)
            submesh = Mesh(sub, rest_names)
            shardings.append(NamedSharding(submesh, P()))
        n_virt = S * self.n_chunks
        for vs in range(n_virt):
            sh = shardings[vs % S]
            for p in self._segment_params(vs):
                if getattr(p, "_dist_meta", None) is not None:
                    continue  # already placed by TP/sharding wrappers
                p._data = jax.device_put(p._data, sh)
        return shardings

    def _collect_segment_params(self, vs: int):
        from ....nn.layer.layers import Layer

        out = []
        for lyr, _ in self._segments[vs]:
            if isinstance(lyr, Layer):
                out.extend(p for p in lyr.parameters()
                           if not p.stop_gradient)
        return out

    def _segment_params(self, vs: int):
        return self._params_of_segment[vs]

    def _to_stage(self, arr, vs: int):
        import jax

        if self._stage_shardings is None:
            return arr
        return jax.device_put(arr, self._stage_shardings[vs % self._layers.num_stages])

    # -- plumbing -----------------------------------------------------------
    def _split_micro(self, data):
        inputs, labels = data
        n = self.accumulate_steps
        bs = inputs.shape[0]
        if bs % n != 0:
            raise ValueError(f"batch {bs} not divisible into {n} micro steps")
        m = bs // n
        micros = []
        for i in range(n):
            sl = slice(i * m, (i + 1) * m)
            micros.append((Tensor(inputs._data[sl],
                                  stop_gradient=inputs.stop_gradient),
                           Tensor(labels._data[sl], stop_gradient=True)))
        return micros

    def _run_segment(self, vs: int, x: Tensor) -> Tensor:
        for lyr, fwd in self._segments[vs]:
            x = fwd(lyr, x) if fwd is not None else lyr(x)
        return x

    # -- the pipelined engine ------------------------------------------------
    def forward_backward_pipeline(self, data, scaler=None):
        from ....core import autograd

        micros = self._split_micro(data)
        M = len(micros)
        S = self._layers.num_stages
        V = self.n_chunks
        n_virt = S * V
        slots = build_schedule(self.schedule, S, M, V)

        store = {}      # (vs, m) -> (x_in, out)  [out = y, or loss at last vs]
        upstream = {}   # (vs, m) -> cotangent for vs's output
        losses = [None] * M
        live = {d: 0 for d in range(S)}
        peak = {d: 0 for d in range(S)}
        self.schedule_log = []
        inv_m = 1.0 / M

        for t, slot in enumerate(slots):
            for c, d, m, op in slot:
                vs = c * S + d
                self.schedule_log.append((t, c, d, m, op))
                if op == "F":
                    if vs == 0:
                        x_in = micros[m][0]
                        if not x_in.stop_gradient:
                            x_in = Tensor(self._to_stage(x_in._data, vs),
                                          stop_gradient=False)
                    else:
                        prev = store[(vs - 1, m)][1]
                        x_in = Tensor(self._to_stage(prev._data, vs),
                                      stop_gradient=False)
                    y = self._run_segment(vs, x_in)
                    if vs == n_virt - 1:
                        loss = self._layers._loss_fn(y, micros[m][1]) \
                            if self._layers._loss_fn else y
                        losses[m] = loss
                        store[(vs, m)] = (x_in, loss)
                    else:
                        store[(vs, m)] = (x_in, y)
                    live[d] += 1
                    peak[d] = max(peak[d], live[d])
                elif op == "BW":
                    # eager engine computes wgrad together with dgrad at the
                    # BX step (a per-stage `paddle.grad` yields both); the
                    # BW slot exists for schedule/bubble accounting
                    continue
                else:  # backward (dgrad[+wgrad]) of virtual stage vs, micro m
                    x_in, out = store.pop((vs, m))
                    live[d] -= 1
                    params = self._segment_params(vs)
                    wants_x = vs > 0 and not x_in.stop_gradient
                    inputs = ([x_in] if wants_x else []) + list(params)
                    if vs == n_virt - 1:
                        seed = out * inv_m
                        if scaler is not None:
                            seed = scaler.scale(seed)
                        grads = autograd.grad([seed], inputs,
                                              allow_unused=True) \
                            if inputs else []
                    else:
                        g = upstream.pop((vs, m))
                        grads = autograd.grad(
                            [out], inputs,
                            grad_outputs=[Tensor(self._to_stage(g._data, vs))],
                            allow_unused=True) if inputs else []
                    gi = 0
                    if wants_x:
                        gx = grads[0]
                        gi = 1
                        if gx is not None:
                            upstream[(vs - 1, m)] = gx
                    for p, gp in zip(params, grads[gi:]):
                        if gp is None:
                            continue
                        if p.grad is None:
                            p.grad = Tensor(gp._data, stop_gradient=True)
                        else:
                            prev = (p.grad.to_dense()
                                    if getattr(p.grad, "is_selected_rows",
                                               False) else p.grad._data)
                            p.grad = Tensor(prev + gp._data,
                                            stop_gradient=True)
        self.peak_live_activations = peak
        total = losses[0]
        for l in losses[1:]:
            total = total + l
        self.total_loss = total * inv_m
        return self.total_loss

    def train_batch(self, data, optimizer, lr_scheduler=None, scaler=None):
        loss = self.forward_backward_pipeline(data, scaler)
        if scaler is not None:
            scaler.step(optimizer)
            scaler.update()
        else:
            optimizer.step()
        optimizer.clear_grad()
        if lr_scheduler is not None:
            lr_scheduler.step()
        return loss

    def eval_batch(self, data, compute_loss=True):
        micros = self._split_micro(data)
        n_virt = self._layers.num_stages * self.n_chunks
        total = None
        from ....core.autograd import no_grad

        with no_grad():
            for x, y in micros:
                for vs in range(n_virt):
                    x = Tensor(self._to_stage(x._data, vs),
                               stop_gradient=True)
                    x = self._run_segment(vs, x)
                out = self._layers._loss_fn(x, y) \
                    if (compute_loss and self._layers._loss_fn) else x
                total = out if total is None else total + out
        return total * (1.0 / len(micros))

    def forward(self, *args, **kwargs):
        if self._stage_shardings is None:
            return self._layers(*args, **kwargs)
        # placed pipeline: chain segments with inter-stage transfers
        x = args[0]
        n_virt = self._layers.num_stages * self.n_chunks
        for vs in range(n_virt):
            x = Tensor(self._to_stage(x._data, vs),
                       stop_gradient=x.stop_gradient)
            x = self._run_segment(vs, x)
        return x

    __call__ = forward

    def __getattr__(self, item):
        return getattr(self._layers, item)


# ---------------------------------------------------------------------------
# the compiled (one-XLA-program) path
# ---------------------------------------------------------------------------

def pipeline_ticks(n_stages: int, n_micro: int, n_chunks: int = 1) -> int:
    """Scan trip count of the compiled pipeline: V*ceil(M/S)*S + S - 1 for
    the interleaved schedule (== V*M + S - 1 when S | M), M + S - 1 for
    V=1. Compiled bubble fraction = 1 - V*M / ticks."""
    S, M, V = int(n_stages), int(n_micro), max(1, int(n_chunks))
    if V == 1:
        return M + S - 1
    import math

    return V * math.ceil(M / S) * S + S - 1


_scan_jit_cache: dict = {}


def scan_pipeline(stage_fn, stage_params, inputs, n_micro: int,
                  axis_name: str = "pp", mesh=None, n_chunks: int = 1):
    """Compiled pipeline as one XLA program (the TPU-native path).

    stage_fn(params, x) -> y: one virtual pipeline stage; per-stage weights
    differ but the pytree structure and the x->y aval must match across
    stages (the transformer-stack case — embed/head belong in
    `first_fn`/`last_fn` of `pipeline_train_step`). x/y may be arbitrary
    pytrees (multi-tensor boundaries).

    stage_params: pytree with leaves stacked [S, ...] (or [S, V, ...] when
    n_chunks=V>1) — stage i's (chunked) weights live on pp coordinate i.
    inputs: pytree of [n_micro, micro_batch, ...] micro stacks.

    Runs inside `shard_map` over the pp axis as ONE `lax.scan`:
    - V=1: at tick t stage s works micro-batch t-s; the carry `ppermute`s
      stage outputs around the ICI ring. Ticks = M + S - 1.
    - V>1 (VPP): the true interleaved schedule inside the SAME scan — at
      tick t, stage s computes chunk c = (t-s) % (S*V) // S of micro-batch
      m = ((t-s) // (S*V)) * S + (t-s) % S (micro-batches in groups of S,
      Megatron interleaved order). Every tick each stage both computes and
      forwards its output, so one scan covers all V chunks and the bubble
      is (S-1)/(V*M + S-1) — V times smaller than V sequential scans.

    Output: pytree of [n_micro, micro_batch, ...] — the LAST stage's
    results, fetched by slicing the pp-stacked shard_map output (a single
    shard transfer, not the old full psum broadcast).
    """
    import jax
    import jax.numpy as jnp

    if mesh is None:
        mesh = _current_mesh()
    S = mesh.shape[axis_name]
    V = max(1, int(n_chunks))
    M = int(n_micro)
    ticks = pipeline_ticks(S, M, V)

    def per_stage(params, xs):
        stage = jax.lax.axis_index(axis_name)
        # drop the shard_map-split stage dim: leaves [V, ...] or [...]
        params = jax.tree.map(lambda p: p[0], params)

        state0 = jax.tree.map(lambda x: jnp.zeros_like(x[0]), xs)
        out0 = jax.tree.map(jnp.zeros_like, xs)

        def step(carry, t):
            state, outputs = carry
            tp = t - stage
            if V == 1:
                c = jnp.int32(0)
                m = tp
            else:
                r = jnp.mod(tp, S * V)
                c = r // S
                m = (tp // (S * V)) * S + jnp.mod(tp, S)
            valid = (tp >= 0) & (m >= 0) & (m < M)
            c = jnp.clip(c, 0, V - 1)
            midx = jnp.clip(m, 0, M - 1)
            inject = (stage == 0) & (c == 0)
            x_in = jax.tree.map(
                lambda xl, st: jnp.where(inject, xl[midx], st), xs, state)
            if V == 1:
                pc = params
            else:
                pc = jax.tree.map(lambda p: jnp.take(p, c, axis=0), params)
            y = stage_fn(pc, x_in)
            # shift outputs to the next stage around the pp ring (ICI);
            # the wrap S-1 -> 0 carries chunk c to chunk c+1 under VPP
            nxt = jax.tree.map(
                lambda a: jax.lax.ppermute(
                    a, axis_name, [(i, (i + 1) % S) for i in range(S)]), y)
            take = valid & (stage == S - 1) & (c == V - 1)
            outputs = jax.tree.map(
                lambda o, yl: jnp.where(take, o.at[midx].set(yl), o),
                outputs, y)
            return (nxt, outputs), None

        (_, outputs), _ = jax.lax.scan(step, (state0, out0),
                                       jnp.arange(ticks))
        # leading unit dim becomes the pp-stacked dim of the global output
        return jax.tree.map(lambda o: o[None], outputs)

    from jax.sharding import PartitionSpec as P

    # only the pp axis is manual; any other mesh axes (dp/mp/sp) stay
    # automatic — GSPMD shards the stage body over them from the data/param
    # shardings, composing pipeline with tensor/data parallelism in ONE
    # program (SURVEY.md §7.3 hard-part 2)
    fn = jax.shard_map(per_stage, mesh=mesh,
                       in_specs=(P(axis_name), P()),
                       out_specs=P(axis_name),
                       axis_names=frozenset({axis_name}), check_vma=False)
    # partial-manual shard_map needs jit to resolve the auto axes (nested
    # jit inlines when the caller is already tracing); the wrapper is
    # cached so repeated eager calls with the same stage_fn/mesh/shape
    # reuse one compiled program
    jitted = _scan_jit_cache.get((stage_fn, mesh, axis_name, V, M))
    if jitted is None:
        if len(_scan_jit_cache) > 64:
            _scan_jit_cache.clear()
        jitted = _scan_jit_cache[(stage_fn, mesh, axis_name, V, M)] = \
            jax.jit(fn)
    stacked_out = jitted(stage_params, inputs)
    # only the last stage's block is real data: one shard fetch, no psum
    return jax.tree.map(lambda o: o[S - 1], stacked_out)


def pipeline_train_step(stage_fn, stacked_params, inputs, labels, *,
                        loss_fn, n_micro: int, axis_name: str = "pp",
                        schedule: str = "1F1B", n_chunks: int = 1,
                        first_fn=None, first_params=None,
                        last_fn=None, last_params=None, mesh=None):
    """Forward + loss + backward of a pipelined model as ONE compilable
    computation. Returns ``(loss, (stacked_grads, first_grads, last_grads))``.

    - `first_fn(first_params, inputs)` runs before the pipeline (embedding),
      `last_fn(last_params, y)` after it (head); both replicated over pp.
    - schedule: "FThenB" saves all scan residuals (peak activation memory
      scales with n_micro); "1F1B"/"VPP" wrap the stage in `jax.checkpoint`
      so backward rematerialises per step — the compiled counterpart of the
      1F1B bounded-memory profile.
    - n_chunks > 1 (VPP): stacked_params leaves carry an extra leading chunk
      dim [V, S, ...]; all V chunks run interleaved inside ONE scan
      (see `scan_pipeline`), so the bubble is (S-1)/(V*M + S-1) — the
      reference `PipelineParallelWithInterleave:1161` profile.

    Differentiating through `ppermute` gives the reverse-direction cotangent
    ring for free — the backward p2p the reference hand-writes.
    """
    import jax
    import jax.numpy as jnp

    sched = schedule.upper().replace("-", "")
    sfn = stage_fn if sched == "FTHENB" else jax.checkpoint(stage_fn)

    def full(all_params, inputs, labels):
        stacked, fp, lp = all_params
        x = first_fn(fp, inputs) if first_fn is not None else inputs
        mb = x.shape[0] // n_micro
        micros = x.reshape((n_micro, mb) + tuple(x.shape[1:]))
        if n_chunks > 1:
            # external layout [V, S, ...] -> scan layout [S, V, ...]
            stacked = jax.tree.map(lambda p: jnp.swapaxes(p, 0, 1), stacked)
        micros = scan_pipeline(sfn, stacked, micros, n_micro, axis_name,
                               mesh=mesh, n_chunks=n_chunks)
        y = micros.reshape((n_micro * mb,) + tuple(micros.shape[2:]))
        out = last_fn(lp, y) if last_fn is not None else y
        return loss_fn(out, labels)

    loss, grads = jax.value_and_grad(full)(
        (stacked_params, first_params, last_params), inputs, labels)
    return loss, grads


def pipeline_layer_to_stage_fn(pipe: PipelineLayer):
    """Bridge a `PipelineLayer` to the compiled path: returns
    ``(stage_fn, stacked_params)`` with per-stage parameter pytrees stacked
    on dim0. Requires stage segments with identical layer/param structure
    (the repeated-block case); raises otherwise."""
    import jax.numpy as jnp

    from ....jit.functional import functional_call
    from ....nn.layer.layers import Layer

    segs = [pipe.stage_layers(s) for s in range(pipe.num_stages)]
    per_stage = []
    for seg in segs:
        ps = []
        for lyr, _ in seg:
            if isinstance(lyr, Layer):
                ps.extend(p for _, p in sorted(lyr.named_parameters()))
        per_stage.append(ps)
    shapes0 = [tuple(p.shape) for p in per_stage[0]]
    for s, ps in enumerate(per_stage[1:], 1):
        if [tuple(p.shape) for p in ps] != shapes0:
            raise ValueError(
                f"stage {s} param structure {[tuple(p.shape) for p in ps]} "
                f"differs from stage 0 {shapes0}; the compiled pipeline "
                "needs homogeneous stages (keep embed/head in "
                "first_fn/last_fn)")
    stacked = {f"p{i}": jnp.stack([jnp.asarray(ps[i]._data)
                                   for ps in per_stage])
               for i in range(len(shapes0))}
    template = segs[0]

    def stage_fn(params, x):
        out = Tensor(x)
        k = 0
        for lyr, fwd in template:
            if isinstance(lyr, Layer):
                names = [n for n, _ in sorted(lyr.named_parameters())]
                sub = {n: params[f"p{k + j}"] for j, n in enumerate(names)}
                k += len(names)
                if fwd is not None:
                    from ....jit.functional import _swapped

                    with _swapped(lyr, sub):
                        out = fwd(lyr, out)
                else:
                    out = functional_call(lyr, sub, out)
            else:
                out = fwd(lyr, out) if fwd is not None else lyr(out)
        return out._data

    return stage_fn, stacked


def _current_mesh():
    hcg = get_hybrid_communicate_group()
    if hcg is None:
        raise RuntimeError("fleet.init first")
    return hcg.get_hybrid_mesh().to_jax_mesh()
