"""Tensor-parallel serving — shard a built engine over a TP×DP mesh.

ROADMAP item 1: everything under `serving/` was single-chip; this module
makes `EngineCore.ragged_step` run TP-sharded the way `quantize_engine`
made it run quantized — an OFFLINE walk over a built engine that swaps
its state for sharded state and returns a drop-in `EngineCore`
(`ShardedEngine`), with the scheduler/radix/COW bookkeeping untouched.

Layout (docs/SERVING.md "Tensor-parallel serving"):

- **Megatron column/row pairing.** The llama stack's fused qkv and
  gate_up projections are column-parallel — their columns are PERMUTED
  first (`_interleave_perm`) so every shard holds whole heads of q|k|v
  (resp. matching gate|up halves) contiguously and the unmodified
  `_layer_body` split arithmetic works on the local shard — and o/down
  are row-parallel, their partial sums psum-reduced over the mesh axis.
  The MLP engine pairs a row-parallel w1 (rows permuted so shard s
  holds the [last_s, mean_s] feature rows) with a column-parallel
  vocab w2. One reduction per pair, exactly Megatron's f/g operators.
- **KV pool shards along the head axis** (llama: `KVH % tp == 0`,
  int8 scale planes split with their heads; MLP: the feature axis).
  Block ids stay LOGICAL — the paged bookkeeping, COW/radix/refcount
  semantics and block tables are replicated and untouched; only the
  per-block payload narrows per chip.
- **Scheduler state is replicated**: the `ShardedEngine` presents the
  same numpy-in/NumPy-or-Array-out `ragged_step`/`verify_step` surface,
  so `Scheduler`/`ServingFrontend` cannot tell it is multichip.
- **Decode finishes device-side**: in overlap mode the vocab-sharded
  logits are all-gathered IN-PROGRAM (`tp_overlap.gather_columns`), so
  the fused sampler consumes replicated logits with no host round-trip
  and sampling is bitwise-equal to the single-chip engine.

Exposure (the perf half, PAPERS.md arXiv 2401.16677): the row-parallel
gemms are decomposed into `overlap_tiles` output tiles
(`distributed/tp_overlap.py`) so tile k's psum runs as an async
`all-reduce-start`/`done` pair concurrent with tile k+1's compute.
`overlap=False` builds the sequential-collective baseline instead —
one undecomposed psum per gemm and a HOST-side logit-shard assembly
(the exposed leg, timed and recorded as a `comms.record("all_gather")`
when observability is on). Both modes wrap the dispatch in
`comms.step_overlap`, so `comm.exposed_ms_per_step` A/Bs the two and
the `serving_tp` bench gates overlap strictly below sequential. The
compiled program's collective census is budgeted in
`analysis/hlo_manifest.json` (`ragged_decode_tp`) — sharding changes
are auditable, not accidental.

Shard BEFORE traffic (like `quantize_engine`): the sharded engine owns
a fresh `BlockCacheManager` with the base engine's geometry, and the
base engine must not serve afterwards from the same logical pool.
"""
from __future__ import annotations

import functools
import time
from typing import Optional

import numpy as np

from .. import observability as _obs
from ..distributed.process_mesh import ProcessMesh
from ..distributed.tp_overlap import TPInfo
from ..inference import kv_migrate
from ..inference.cache import BlockCacheManager
from ..inference.step_engine import StepEngine
from ..observability import comms

__all__ = ["ShardingConfigError", "shard_engine", "ShardedEngine"]


class ShardingConfigError(ValueError):
    """A TP/DP layout that cannot be built — raised by `shard_engine`
    BEFORE any device allocation (pure shape/topology arithmetic), so a
    bad config never leaves half-sharded state or a dead mesh behind."""


# ---------------------------------------------------------------------------
# layout arithmetic (pure numpy — runs before any device work)
# ---------------------------------------------------------------------------

def _interleave_perm(sizes, tp: int) -> np.ndarray:
    """Column permutation for a FUSED column-parallel weight whose output
    axis concatenates segments of `sizes` (qkv: [nh*d, kvh*d, kvh*d];
    gate_up: [I, I]; the MLP head input: [D, D]): shard s's contiguous
    chunk becomes [seg0_s, seg1_s, ...], so the engine's unmodified
    split arithmetic works on the local shard."""
    offs = np.cumsum([0] + list(sizes[:-1]))
    out = []
    for s in range(tp):
        for off, size in zip(offs, sizes):
            step = size // tp
            out.extend(range(off + s * step, off + (s + 1) * step))
    return np.asarray(out, dtype=np.int64)


def _permute_cols(w, perm):
    """Apply an output-channel permutation: dense [..., K, N] last axis;
    quantized dicts permute the N axis of q/q4 and s."""
    if isinstance(w, dict):
        out = dict(w)
        key = "q4" if "q4" in w else "q"
        out[key] = w[key][..., perm, :]
        out["s"] = w["s"][..., perm]
        return out
    return w[..., perm]


def _shard_rows(w, tp: int, perm=None):
    """Prepare a ROW-parallel weight so that contiguous K-axis sharding
    yields each shard's correct local weight: dense [..., K, N] rows are
    permuted (`perm`, optional), int8 dicts permute the K axis of q, and
    int4 dicts — packed SPLIT-HALF (`nn.quant.pack_int4`: byte j holds
    k=j and k=j+K/2, so the packed axis can neither be permuted nor
    sliced element-wise) — are unpacked, permuted, and REPACKED PER
    SHARD CHUNK, so shard s's contiguous packed slice is exactly the
    split-half pack of its local K rows. Per-OUT-channel scales are
    untouched (every shard needs every output's scale)."""
    if isinstance(w, dict):
        out = dict(w)
        if "q4" in w:
            import jax.numpy as jnp

            from ..nn.quant import pack_int4, unpack_int4

            q = unpack_int4(w["q4"])                     # [..., N, K]
            if perm is not None:
                q = q[..., perm]
            chunk = q.shape[-1] // tp
            out["q4"] = jnp.concatenate(
                [pack_int4(q[..., i * chunk:(i + 1) * chunk])
                 for i in range(tp)], axis=-1)
        elif perm is not None:
            out["q"] = w["q"][..., perm]
        return out
    if perm is not None:
        return w[..., perm, :]
    return w


def _wspec(w, mode: str):
    """PartitionSpec tree for one gemm weight. Dense weights are
    [..., K, N]; quantized dicts are {q|q4 [..., N, K(/2)], s [..., N]}.
    "col" shards the output (N) axis, "row" shards the input (K) axis
    (quantized row shards keep per-out-channel scales replicated —
    every shard needs every output's scale)."""
    from jax.sharding import PartitionSpec as P

    if isinstance(w, dict):
        key = "q4" if "q4" in w else "q"
        lead = (None,) * (w[key].ndim - 2)
        if mode == "col":
            return {key: P(*lead, "tp", None), "s": P(*lead, "tp")}
        return {key: P(*lead, None, "tp"), "s": P(*lead)}
    lead = (None,) * (w.ndim - 2)
    if mode == "col":
        return P(*lead, None, "tp")
    return P(*lead, "tp", None)


def _even(name: str, n: int, tp: int, why: str):
    if n % tp:
        raise ShardingConfigError(
            f"{name}={n} is not divisible by tp={tp} — {why}")


def _validate_llama(engine, tp: int):
    cfg = engine.config
    _even("num_key_value_heads", cfg.num_key_value_heads, tp,
          "the paged KV pool shards along the head axis (KVH % tp == 0)")
    _even("num_attention_heads", cfg.num_attention_heads, tp,
          "qkv is column-parallel over whole query heads")
    _even("intermediate_size", cfg.intermediate_size, tp,
          "gate_up/down split the MLP width")
    head = engine.params.get("lm_head")
    if head is not None:
        v = int(head["s"].shape[-1] if isinstance(head, dict)
                else head.shape[-1])
        _even("vocab_size", v, tp,
              "the untied lm_head is vocab-column-parallel")
    for key, k_in in (("o_w", cfg.num_attention_heads * cfg.head_dim),
                      ("down_w", cfg.intermediate_size)):
        w = engine.params.get(key)
        if isinstance(w, dict) and "q4" in w and (k_in // tp) % 2:
            raise ShardingConfigError(
                f"int4 {key}: per-shard in_features {k_in}//{tp} is odd "
                "— the packed byte pairs cannot split across shards")


def _validate_mlp(engine, tp: int):
    d = int(engine.params["embed"].shape[1])
    _even("hidden", d, tp,
          "the embedding pool and w1 rows shard along the feature axis")
    _even("vocab_size", int(engine.vocab_size), tp,
          "w2/b2 are vocab-column-parallel")
    w1 = engine.params.get("w1")
    if isinstance(w1, dict) and "q4" in w1 and (d // tp) % 2:
        raise ShardingConfigError(
            f"int4 w1: per-shard feature slice {d}//{tp} is odd — the "
            "packed byte pairs cannot split across shards")


# ---------------------------------------------------------------------------
# the offline pass
# ---------------------------------------------------------------------------

def shard_engine(engine, mesh: Optional[ProcessMesh] = None, *,
                 tp: int = 2, dp: int = 1, overlap: bool = True,
                 overlap_tiles: int = 4) -> "ShardedEngine":
    """Walk a built serving engine (full-precision OR `quantize_engine`
    int8/int4 weight-only, either KV mode) and return a TP-sharded
    `ShardedEngine` serving the same `ragged_step`/`verify_step`/
    `copy_kv_block` surface over a (dp, tp) device mesh.

    `mesh` is an optional `ProcessMesh` slice naming the processes to
    shard over (size must be exactly tp*dp; row-major → (dp, tp));
    default: the first tp*dp visible devices. `dp` replicates the whole
    engine — compute and KV — across data-parallel rows (specs never
    name the dp axis); request routing across replicas stays the
    frontend's business, matching "scheduler state stays replicated".

    `overlap=True` (the shipped mode) decomposes each row-parallel gemm
    into `overlap_tiles` psum tiles and all-gathers logits in-program;
    `overlap=False` builds the sequential-collective baseline the bench
    A/Bs (one psum per gemm, host-side logit assembly). All layout
    problems raise `ShardingConfigError` before any device allocation.
    """
    if isinstance(engine, ShardedEngine):
        raise ShardingConfigError("engine is already TP-sharded — "
                                  "shard the underlying engine once")
    tp, dp = int(tp), int(dp)
    if tp < 1 or dp < 1:
        raise ShardingConfigError(
            f"tp and dp must be >= 1, got tp={tp} dp={dp}")
    params = getattr(engine, "params", None)
    if not isinstance(params, dict):
        raise ShardingConfigError(
            f"{type(engine).__name__} has no params dict to shard")
    if "qkv_w" in params:
        kind = "llama"
        _validate_llama(engine, tp)
    elif "w1" in params:
        kind = "mlp"
        _validate_mlp(engine, tp)
    else:
        raise ShardingConfigError(
            f"{type(engine).__name__}: unrecognized parameter layout "
            "(expected llama projection keys or MLP w1/w2)")
    if mesh is not None:
        if int(mesh.size) != tp * dp:
            raise ShardingConfigError(
                f"mesh has {mesh.size} processes but tp*dp = {tp * dp} "
                f"(tp={tp}, dp={dp}) — slice the mesh "
                "(get_mesh_with_dim) before sharding")
        ids = np.asarray(mesh.process_ids, np.int64)
    else:
        ids = np.arange(tp * dp, dtype=np.int64)
    import jax

    ndev = jax.device_count()
    if tp * dp > ndev:
        raise ShardingConfigError(
            f"tp*dp = {tp * dp} exceeds the {ndev} visible devices")
    pmesh = ProcessMesh(ids.reshape(dp, tp), ["dp", "tp"])
    return ShardedEngine(engine, pmesh, tp=tp, dp=dp, kind=kind,
                         overlap=bool(overlap),
                         overlap_tiles=int(overlap_tiles))


class ShardedEngine(kv_migrate.PagedPools, StepEngine):
    """TP-sharded `EngineCore`: the shell's dispatch surfaces
    (`inference.step_engine.StepEngine`) over shard_map'd executables,
    `copy_kv_block` and KV migration over the sharded pool tuple
    (`kv_migrate.PagedPools`: block ids are logical, the sharded
    head/feature axis is untouched, so every chip moves its own slice
    with no collective, and a payload's slabs stay TP-sharded — the
    header's `tp` pins that they only inject into an identically-sharded
    engine), plus the observability hooks (`cost_card_args` lowers the
    SPMD program, so the CostCard reports PER-CHIP FLOPs; `quant_info`
    reports per-chip KV bytes)."""

    def __init__(self, base, pmesh: ProcessMesh, *, tp: int, dp: int,
                 kind: str, overlap: bool, overlap_tiles: int):
        import jax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        self._jax = jax
        self.mesh = pmesh
        self.tp, self.dp = tp, dp
        self.overlap = overlap
        self._kind = kind
        self.tpinfo = TPInfo("tp", tp, overlap_tiles if overlap else 1,
                             gather_logits=overlap)
        self.kv_bits = int(getattr(base, "kv_bits", 16))
        self.max_batch_size = base.max_batch_size
        self.block_size = base.block_size
        self.weight_only = getattr(base, "weight_only", None)
        if hasattr(base, "vocab_size"):
            self.vocab_size = base.vocab_size
        # fresh paged bookkeeping, same LOGICAL geometry — block ids and
        # tables are replicated; only the per-block payload narrows
        m = base.manager
        self.manager = BlockCacheManager(m.num_blocks, m.block_size,
                                         m.max_blocks_per_seq)
        jmesh = pmesh.to_jax_mesh()
        self._jmesh = jmesh
        R = P()

        def put(v, spec):
            if isinstance(v, dict):
                return {k: jax.device_put(x, NamedSharding(jmesh, spec[k]))
                        for k, x in v.items()}
            return jax.device_put(v, NamedSharding(jmesh, spec))

        if kind == "llama":
            from ..inference import kv_quant
            from ..inference.llama_runner import (_head, _ragged_stack,
                                                  _StaticCfg)

            cfg = base.config
            nh, kvh, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                          cfg.head_dim)
            p = dict(base.params)
            p["qkv_w"] = _permute_cols(
                p["qkv_w"], _interleave_perm([nh * d, kvh * d, kvh * d], tp))
            p["gate_up_w"] = _permute_cols(
                p["gate_up_w"],
                _interleave_perm([cfg.intermediate_size] * 2, tp))
            p["o_w"] = _shard_rows(p["o_w"], tp)
            p["down_w"] = _shard_rows(p["down_w"], tp)
            pspec = {k: R for k in p}
            pspec["qkv_w"] = _wspec(p["qkv_w"], "col")
            pspec["gate_up_w"] = _wspec(p["gate_up_w"], "col")
            pspec["o_w"] = _wspec(p["o_w"], "row")
            pspec["down_w"] = _wspec(p["down_w"], "row")
            vocab_sharded = "lm_head" in p
            if vocab_sharded:
                pspec["lm_head"] = _wspec(p["lm_head"], "col")
            self.params = {k: put(v, pspec[k]) for k, v in p.items()}
            kvspec = P(None, None, "tp", None, None)
            # int8 scale planes split with their heads
            poolspec = (kvspec, kvspec, P(None, None, "tp", None),
                        P(None, None, "tp", None))[:len(base.pools)]
            lcfg = _StaticCfg(cfg)
            lcfg.num_heads //= tp
            lcfg.num_kv_heads //= tp
            lcfg.tp = self.tpinfo
            lspec = R if (overlap or not vocab_sharded) else P(None, "tp")
            stack, head = (functools.partial(fn, cfg=lcfg)
                           for fn in (_ragged_stack, _head))
            # the row-parallel gemms' psums leave every shard the whole
            # hidden row
            hspec = R
            geom = dict(base._kv_geom)
            geom["kv_heads"] //= tp
            self._kv_bytes_per_token = kv_quant.kv_bytes_per_token(**geom)
            self.manager.set_kv_geometry(
                kv_quant.kv_bytes_per_block(**geom), self.kv_bits)
            header = {"engine": "llama",
                      "num_layers": geom["num_layers"],
                      "kv_heads": base._kv_geom["kv_heads"],
                      "head_dim": geom["head_dim"]}
        else:
            from .engine import _mlp_head, _mlp_ragged_stack

            d = int(base.params["embed"].shape[1])
            p = dict(base.params)
            p["w1"] = _shard_rows(p["w1"], tp, _interleave_perm([d, d], tp))
            pspec = {"embed": R, "b1": R,
                     "w1": _wspec(p["w1"], "row"),
                     "w2": _wspec(p["w2"], "col"),
                     "b2": P("tp")}
            self.params = {k: put(v, pspec[k]) for k, v in p.items()}
            # the int8 scale plane stays REPLICATED: absmax is over
            # the FULL feature vector (bitwise parity), so every
            # shard holds every slot's scale
            poolspec = (P(None, None, "tp"), R)[:len(base.pools)]
            lspec = R if overlap else P(None, "tp")
            stack = functools.partial(_mlp_ragged_stack,
                                      block_size=base.block_size,
                                      tp=self.tpinfo)
            head = functools.partial(_mlp_head, tp=self.tpinfo)
            # each shard's [own embedding, window mean] feature slices,
            # side by side in the order of `w1`'s permuted rows
            hspec = P(None, "tp")
            bpb = base.block_size * (d // tp) * base.pools[0].dtype.itemsize \
                + base.block_size * 4 * (len(base.pools) - 1)
            self._kv_bytes_per_token = bpb / base.block_size
            self.manager.set_kv_geometry(bpb, self.kv_bits)
            header = {"engine": "mlp", "hidden": d}

        self.pools = tuple(put(p, s) for p, s in zip(base.pools, poolspec))
        # the stack is one shard_map and the head another, in the SAME
        # jit: between them the tail gathers each lane's last hidden row
        # (`ops/sampling.with_tail`), so the head's gemms and its
        # collective run over `B` rows, and the screen and the sampler
        # follow over whatever layout the logits leave the head in: vocab
        # shards in sequential mode, replicated rows under overlap; the
        # verify program is the same two over a window a lane
        stack = jax.shard_map(
            stack, mesh=jmesh, in_specs=(pspec, poolspec, R, R, R, R),
            out_specs=(hspec, poolspec), check_vma=False)
        rows_head = jax.shard_map(
            lambda params, x: head((params,), x, None), mesh=jmesh,
            in_specs=(pspec, hspec), out_specs=lspec, check_vma=False)

        def sharded_head(state, x, lane):
            return rows_head(state[0], x)

        self._build_programs(stack, sharded_head)
        # the last step's `sampled`, replicated as the step leaves it: a
        # host array first would key a second executable
        self.last_sampled = put(
            np.zeros((2, self.max_batch_size), np.int32), R)
        self._step_label = f"serving.ragged_step_tp{tp}"
        # COW copy and KV migration index the LOGICAL block axis, which is
        # unsharded in both layouts: shardings propagate through the
        # jits, each chip moves its own slice
        self._build_block_ops(1 if kind == "llama" else 0)
        self._slab_names = tuple(f"p{i}" for i in range(len(self.pools)))
        self._mig_header = dict(
            header, version=kv_migrate.PAYLOAD_VERSION,
            block_size=base.block_size,
            max_blocks_per_seq=self.manager.max_blocks_per_seq,
            kv_bits=self.kv_bits, tp=tp, dtype=str(self.pools[0].dtype))

    # ---- observability surface ----
    def tp_summary(self) -> dict:
        """The sharding mode, for bench extras / reports."""
        return {"kind": self._kind, "tp": self.tp, "dp": self.dp,
                "overlap": self.overlap, "tiles": self.tpinfo.tiles,
                "mesh": self.mesh.describe(),
                "kv_bytes_per_token_per_chip": self._kv_bytes_per_token}

    def quant_info(self) -> dict:
        """Same surface as the base engines; `kv_bytes_per_token` is the
        PER-CHIP cost — the number that divides each chip's HBM."""
        wb = {"int8": 8, "int4": 4, "fp8": 8}.get(self.weight_only, 16)
        if self._kind == "mlp":
            w1 = self.params.get("w1")
            if isinstance(w1, dict):
                wb = 4 if "q4" in w1 else 8
        return {"wbits": wb, "kv_bits": self.kv_bits,
                "kv_bytes_per_token": self._kv_bytes_per_token}

    def kv_bytes_per_token(self) -> float:
        return self._kv_bytes_per_token

    def _run(self, fn, *args):
        """One of the step programs over this engine's pools, which it
        replaces; what the program returns ahead of them. With
        observability on, the dispatch runs inside a `comms.step_overlap`
        window — overlap mode exposes ~0 collective ms (everything is
        in-program), sequential mode's host logit assembly is recorded as
        an exposed all_gather."""
        if _obs.enabled():
            with comms.step_overlap(self._step_label):
                return self._dispatch(fn, True, *args)
        return self._dispatch(fn, False, *args)

    def _dispatch(self, fn, obs_on, *args):
        """Run one step executable over exact-dtype call arrays. The
        round's `sampled` stays on the device in either mode (its sampler
        ran in-program, over the head's `B` rows); logits (the verify
        step's, the all-rows program's) leave sequential mode through
        the host."""
        out = StepEngine._run(self, fn, *args)
        if self.overlap or fn is self._ragged:
            if obs_on:
                self._jax.block_until_ready(out)
            return out
        # sequential-collective baseline: the vocab shards cross to
        # the host and reassemble here, fully exposed — the leg the
        # tiled in-program psums + device all-gather delete
        self._jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = np.asarray(out)
        if _obs.enabled():
            comms.record("all_gather", self.tp, out.nbytes, t0,
                         time.perf_counter() - t0)
        return out
