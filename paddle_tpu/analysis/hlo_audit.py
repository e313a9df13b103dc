"""ptlint tier B: compiled-artifact audit against a committed manifest.

PR 8 built `hlo_comm_census` — the comm volume of a compiled program,
parsed from optimized HLO — but nothing *gated* on it: a stray
`device_get` on the decode path, an accidental collective from a
resharding change, or a silent f32 upcast inside a declared-bf16 program
would only surface as a TPU bill. This module lowers the REGISTERED
bench executables (the same programs `bench.py` times) and checks each
compiled artifact against `hlo_manifest.json`:

- ``host_transfer_ops_max`` — infeed/outfeed/send/recv + host custom
  calls. The decode path's budget is ZERO: the whole PR 9/10 discipline
  (device-side gather/sampling, exact-dtype numpy into the C++ dispatch
  path) exists so no per-token host round-trip survives compilation.
- ``collective_ops_max`` — total collective instructions
  (`hlo_comm_census`, PR 8). Single-chip programs budget zero; the
  TP-sharded serving step (`ragged_decode_tp`, ISSUE 16) budgets its
  exact census.
- ``collective_budget`` — per-KIND op ceilings for sharded programs
  (``{"all_reduce": 8, "all_gather": 1}``, census kind names). Kinds
  the census finds but the budget does not name are findings: a
  resharding change must re-budget its comm profile deliberately, not
  smuggle a new collective kind under the total.
- ``collective_bytes_max`` — cap on the census' total per-step comm
  bytes; the T3 tiling keeps ops high but bytes flat, and this is the
  key that catches a decomposition silently inflating payloads.
- ``declared_dtype`` — ``"bf16"`` forbids f32 ``dot``/``convolution``
  results (a silent upcast doubles gemm bytes and halves MXU rate);
  f32 programs declare ``"f32"`` and skip the check.
- ``op_budget`` — optional per-op ceilings (``{"dot": 4}``) for
  executables whose op mix is itself the contract.

A violation exits 1 through `tools/ptlint.py --hlo-audit`; an unusable
manifest (unknown key, unregistered executable) exits 2 — mirroring
bench_diff conventions. Unlike tier A this NEEDS jax (it compiles);
keep it out of the tier-1 fast gate and in the smoke/test tier.

The registered executables deliberately use the tiny CPU-shaped
configs: the INVARIANTS audited (no host transfer, no collective, no
upcast) are shape-independent, so the cheap lowering proves the same
contract the production shapes carry. docs/STATIC_ANALYSIS.md covers
the manifest-update workflow.
"""
from __future__ import annotations

import json
import os
import re
from typing import Dict, List, Optional, Tuple

__all__ = ["DEFAULT_MANIFEST", "ManifestError", "EXECUTABLES",
           "lower_executable", "host_transfer_census", "dtype_gemm_census",
           "op_census", "audit_text", "run_audit"]

DEFAULT_MANIFEST = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "hlo_manifest.json")

_KNOWN_KEYS = {"host_transfer_ops_max", "collective_ops_max",
               "collective_bytes_max", "collective_budget",
               "declared_dtype", "op_budget", "note"}


class ManifestError(ValueError):
    """Unusable manifest — a config error (exit 2), not a finding."""


# ---------------------------------------------------------------------------
# HLO text scans (pure; unit-testable without jax)
# ---------------------------------------------------------------------------

# "<result-shape> <op>(" after " = " — same grammar hlo_comm_census uses
_RESULT_OP_RE = re.compile(
    r"((?:\([^)]*\))|(?:[a-z]+[0-9]*\[[^\]]*\](?:\{[^}]*\})?))\s+"
    r"([a-z][\w-]*)\(")

_HOST_TRANSFER_OPS = {"infeed", "outfeed", "send", "send-done", "recv",
                      "recv-done"}
_HOST_CUSTOM_CALL_RE = re.compile(
    r"custom_call_target=\"[^\"]*(?:MoveToHost|MoveToDevice|HostCompute|"
    r"callback)[^\"]*\"")   # xla_python_cpu_callback / xla_ffi_python_*
                            # — io_callback/pure_callback/debug.print all
                            # compile to a host round-trip per call
_GEMM_OPS = {"dot", "convolution"}


def _iter_ops(hlo_text: str):
    """Yield (result_spec, op, line) for every instruction line."""
    for line in hlo_text.splitlines():
        line = line.strip()
        if " = " not in line:
            continue
        m = _RESULT_OP_RE.match(line.split(" = ", 1)[1])
        if m is not None:
            yield m.group(1), m.group(2), line


def op_census(hlo_text: str) -> Dict[str, int]:
    out: Dict[str, int] = {}
    for _res, op, _line in _iter_ops(hlo_text):
        out[op] = out.get(op, 0) + 1
    return out


def host_transfer_census(hlo_text: str) -> int:
    """Instructions that move data across the host boundary: the ops a
    decode-path executable must compile ZERO of."""
    n = 0
    for _res, op, line in _iter_ops(hlo_text):
        if op in _HOST_TRANSFER_OPS:
            n += 1
        elif op.startswith("custom-call") and _HOST_CUSTOM_CALL_RE.search(
                line):
            n += 1
    return n


def dtype_gemm_census(hlo_text: str) -> Dict[str, int]:
    """Gemm (dot/convolution) counts keyed by RESULT dtype — the
    upcast scan: a declared-bf16 program compiling `f32[...] dot(...)`
    pays double HBM traffic and half MXU rate, silently."""
    out: Dict[str, int] = {}
    for res, op, _line in _iter_ops(hlo_text):
        if op not in _GEMM_OPS:
            continue
        m = re.match(r"\(?([a-z]+[0-9]*)\[", res)
        dtype = m.group(1) if m else "unknown"
        out[dtype] = out.get(dtype, 0) + 1
    return out


def audit_text(hlo_text: str, entry: dict,
               platform: Optional[str] = None) -> Tuple[dict, List[str]]:
    """Check one compiled program's text against one manifest entry.
    Returns (actuals, findings). Pure — the doctored-manifest tests and
    any offline HLO dump ride this directly.

    `platform`: the backend the text was compiled FOR. The
    ``declared_dtype: bf16`` upcast scan only binds on ``"tpu"`` (or
    ``None`` = caller-audited text, the strict default): CPU/GPU
    legalization rewrites every bf16 dot to f32 regardless of the
    program, so off-TPU the scan has no signal and is recorded as
    skipped instead of failing a contract the platform cannot
    satisfy."""
    unknown = set(entry) - _KNOWN_KEYS
    if unknown:
        raise ManifestError(f"unknown manifest key(s): {sorted(unknown)} "
                            f"(known: {sorted(_KNOWN_KEYS)})")
    from ..observability.comms import hlo_comm_census

    census = hlo_comm_census(hlo_text)
    collective_ops = sum(e["ops"] for e in census.values())
    host = host_transfer_census(hlo_text)
    gemms = dtype_gemm_census(hlo_text)
    ops = op_census(hlo_text)
    actuals = {
        "host_transfer_ops": host,
        "collective_ops": collective_ops,
        "collective_census": census,
        "gemms_by_dtype": gemms,
        "f32_gemms": gemms.get("f32", 0),
        "total_ops": sum(ops.values()),
    }
    findings: List[str] = []
    host_max = entry.get("host_transfer_ops_max", 0)
    if host > host_max:
        findings.append(
            f"host_transfer_ops {host} > budget {host_max} — a compiled "
            "host round-trip entered the program (device_get / callback "
            "/ infeed); on the decode path that is a per-token stall")
    coll_max = entry.get("collective_ops_max", 0)
    if collective_ops > coll_max:
        findings.append(
            f"collective_ops {collective_ops} > budget {coll_max} "
            f"(census: { {k: v['ops'] for k, v in census.items()} }) — "
            "the program's comm profile changed; re-budget the manifest "
            "deliberately if the sharding change is intentional")
    kind_budget = entry.get("collective_budget")
    if kind_budget is not None:
        for kind, e in sorted(census.items()):
            cap = kind_budget.get(kind)
            if cap is None:
                findings.append(
                    f"collective_budget: unbudgeted collective kind "
                    f"{kind!r} x{e['ops']} — a new collective kind "
                    "entered the program; name it in the manifest "
                    "deliberately")
            elif e["ops"] > int(cap):
                findings.append(
                    f"collective_budget: {kind} x{e['ops']} > budget "
                    f"{cap}")
    bytes_max = entry.get("collective_bytes_max")
    collective_bytes = sum(e["bytes"] for e in census.values())
    actuals["collective_bytes"] = collective_bytes
    if bytes_max is not None and collective_bytes > bytes_max:
        findings.append(
            f"collective_bytes {collective_bytes} > budget {bytes_max} "
            "— the step's comm payload grew (tiling must keep bytes "
            "flat while splitting ops)")
    declared = entry.get("declared_dtype")
    if declared == "bf16" and platform not in (None, "tpu"):
        actuals["declared_dtype_check"] = (
            f"skipped on {platform}: bf16 gemms legalize to f32 off-TPU, "
            "so the upcast scan only binds on tpu")
    elif declared == "bf16" and gemms.get("f32", 0) > 0:
        findings.append(
            f"declared-bf16 program compiles {gemms['f32']} f32 gemm(s) "
            "— a silent upcast (double gemm bytes, half MXU rate)")
    for op, budget in (entry.get("op_budget") or {}).items():
        have = ops.get(op, 0)
        if have > int(budget):
            findings.append(f"op_budget: {op} x{have} > budget {budget}")
    return actuals, findings


# ---------------------------------------------------------------------------
# registered executables (jax from here on)
# ---------------------------------------------------------------------------


def _exe_ragged_decode():
    """The serving decode program: `MLPLMEngine._ragged` at the packed
    shapes the scheduler dispatches (decode lanes + prefill chunk in ONE
    fixed-shape executable, PR 9). The jit the scheduler's `serve.decode`
    cost card lowers."""
    import numpy as np

    from ..ops.sampling import step_args
    from ..serving.engine import MLPLMEngine

    eng = MLPLMEngine(vocab_size=64, hidden=16, max_batch_size=4,
                      num_blocks=16, block_size=4, max_blocks_per_seq=4)
    B, T = 4, 4 + 8                       # max_batch + chunk budget
    tokens = np.zeros((T,), np.int32)
    q_lens = np.array([1, 1, 2, 0], np.int32)
    kv_lens = np.array([3, 1, 2, 0], np.int32)
    tables = np.zeros((B, 4), np.int32)
    return eng._ragged, (eng.params, eng.pools,
                         *step_args(tokens, q_lens, kv_lens, tables))


def _exe_verify():
    """The speculative verify program ([B, K+1] window over the ragged
    substrate)."""
    import numpy as np

    from ..serving.engine import MLPLMEngine

    eng = MLPLMEngine(vocab_size=64, hidden=16, max_batch_size=4,
                      num_blocks=16, block_size=4, max_blocks_per_seq=4)
    B, S = 4, 3
    tokens = np.zeros((B, S), np.int32)
    ctx = np.full((B,), S, np.int32)
    tables = np.zeros((B, 4), np.int32)
    return eng._verify, (eng.params, eng.pools, tokens, ctx, tables)


def _exe_sampler():
    """The fused device sampler (`ops/sampling.py`) at the decode shape
    [B, 1, V] — the program that replaced per-lane host numpy sampling
    (PR 4); it must stay free of host transfers itself."""
    import numpy as np

    from ..ops.sampling import _jitted

    B, V = 4, 64
    logits = np.zeros((B, 1, V), np.float32)
    return _jitted(), (logits, np.zeros((B,), np.float32),
                      np.zeros((B,), np.int32), np.zeros((B,), np.int32),
                      np.zeros((B,), np.int32))


def _exe_ragged_decode_quant():
    """The QUANTIZED serving decode program (PR 14): `MLPLMEngine` with
    an int8 KV pool (`kv_bits=8`) and int8 weight-only gemms
    (`serving.quant.quantize_engine`), at the same packed shapes as
    `ragged_decode`. Its compiled form must stay as host-transfer-free
    and collective-free as the full-precision twin — quantize-on-write,
    in-kernel dequant, and the dequant-fused weight gemms are all
    device-side by construction, and this entry keeps them that way."""
    import numpy as np

    from ..ops.sampling import step_args
    from ..serving.engine import MLPLMEngine
    from ..serving.quant import quantize_engine

    eng = quantize_engine(
        MLPLMEngine(vocab_size=64, hidden=16, max_batch_size=4,
                    num_blocks=16, block_size=4, max_blocks_per_seq=4,
                    kv_bits=8), wbits=8)
    B, T = 4, 4 + 8                       # max_batch + chunk budget
    tokens = np.zeros((T,), np.int32)
    q_lens = np.array([1, 1, 2, 0], np.int32)
    kv_lens = np.array([3, 1, 2, 0], np.int32)
    tables = np.zeros((B, 4), np.int32)
    return eng._ragged, (eng.params, eng.pools,
                         *step_args(tokens, q_lens, kv_lens, tables))


def _exe_ragged_decode_lora():
    """The MULTI-LoRA serving decode program (ISSUE 18): the MLP audit
    engine through `serving.lora.attach_adapters` with one resident
    adapter, at the same packed shapes as `ragged_decode`. The per-lane
    adapter ids enter as one [B] int32 argument riding the ragged
    metadata (data, not shape), the batched A/B gathers and the two thin
    low-rank einsums are device-side by construction — so the compiled
    form must stay exactly as host-transfer-free and collective-free as
    the base decode program across ANY adapter mix."""
    import numpy as np

    from ..ops.sampling import step_args
    from ..serving.engine import MLPLMEngine
    from ..serving.lora import attach_adapters, random_adapter

    eng = attach_adapters(
        MLPLMEngine(vocab_size=64, hidden=16, max_batch_size=4,
                    num_blocks=16, block_size=4, max_blocks_per_seq=4),
        pool_slots=2, rank_buckets=(2, 4))
    eng.adapter_pool.register("audit", random_adapter(eng, rank=4))
    eng.adapter_pool.pin("audit")
    B, T = 4, 4 + 8                       # max_batch + chunk budget
    tokens = np.zeros((T,), np.int32)
    q_lens = np.array([1, 1, 2, 0], np.int32)
    kv_lens = np.array([3, 1, 2, 0], np.int32)
    tables = np.zeros((B, 4), np.int32)
    fn, lead = eng.cost_card_args("ragged")
    return fn, (*lead, *step_args(tokens, q_lens, kv_lens, tables))


def _exe_ragged_decode_tp():
    """The TP-SHARDED serving decode program (ISSUE 16): the MLP audit
    engine through `serving.tp.shard_engine(tp=2, overlap=True)` at the
    same packed shapes as `ragged_decode`. Its manifest entry budgets
    the exact deliberate census — the tiled row-parallel psums
    (all_reduce) plus ONE logit all_gather — with a byte cap (tiling
    splits ops, never grows bytes) and ZERO host transfers: decode
    finishes with a device-side gathered logit shard, never a host
    assembly. Needs >= 2 devices (ptlint --hlo-audit forces an 8-device
    CPU topology before importing jax)."""
    import numpy as np

    from ..ops.sampling import step_args
    from ..serving.engine import MLPLMEngine
    from ..serving.tp import shard_engine

    eng = shard_engine(
        MLPLMEngine(vocab_size=64, hidden=16, max_batch_size=4,
                    num_blocks=16, block_size=4, max_blocks_per_seq=4),
        tp=2, overlap=True, overlap_tiles=2)
    B, T = 4, 4 + 8                       # max_batch + chunk budget
    tokens = np.zeros((T,), np.int32)
    q_lens = np.array([1, 1, 2, 0], np.int32)
    kv_lens = np.array([3, 1, 2, 0], np.int32)
    tables = np.zeros((B, 4), np.int32)
    fn, lead = eng.cost_card_args("ragged")
    return fn, (*lead, *step_args(tokens, q_lens, kv_lens, tables))


def _exe_verify_tp():
    """The TP-sharded speculative verify program (same sharded substrate
    as `ragged_decode_tp`, [B, K+1] window) — spec must stay as
    device-side under TP as plain decode."""
    import numpy as np

    from ..serving.engine import MLPLMEngine
    from ..serving.tp import shard_engine

    eng = shard_engine(
        MLPLMEngine(vocab_size=64, hidden=16, max_batch_size=4,
                    num_blocks=16, block_size=4, max_blocks_per_seq=4),
        tp=2, overlap=True, overlap_tiles=2)
    B, S = 4, 3
    tokens = np.zeros((B, S), np.int32)
    ctx = np.full((B,), S, np.int32)
    tables = np.zeros((B, 4), np.int32)
    fn, lead = eng.cost_card_args("verify")
    return fn, (*lead, tokens, ctx, tables)


def _exe_quant_matmul():
    """The weight-only dequant gemm (`nn.quant.dequant_matmul`) at an
    aligned bf16 x int8 shape — the executable every quantized engine's
    projection matmuls route through. The audit pins zero host
    transfers and no f32 gemm under the declared bf16 activations (the
    int8->bf16 convert must fuse into the dot, not upcast it)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..nn.quant import dequant_matmul

    rng = np.random.default_rng(0)
    M, K, N = 8, 128, 128
    x = jnp.asarray(rng.normal(0, 1, (M, K)), jnp.bfloat16)
    wq = jnp.asarray(rng.integers(-127, 128, (N, K)), jnp.int8)
    scale = jnp.asarray(rng.uniform(0.01, 0.1, (N,)), jnp.float32)
    return jax.jit(lambda a, w, s: dequant_matmul(a, w, s)), \
        (x, wq, scale)


def _exe_train_step():
    """A fused fwd+grad+update train step with DONATED state — the
    optimizer.py shape (jit(step, donate_argnums=...)), self-contained
    so the audit doesn't depend on model zoo imports."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    d, h, out = 8, 16, 4

    def train_step(params, moments, x, y):
        def loss_fn(p):
            pred = jnp.tanh(x @ p["w1"]) @ p["w2"]
            return ((pred - y) ** 2).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_m = jax.tree_util.tree_map(lambda m, g: 0.9 * m + g,
                                       moments, grads)
        new_p = jax.tree_util.tree_map(lambda p, m: p - 0.05 * m,
                                       params, new_m)
        return new_p, new_m, loss

    rng = np.random.default_rng(0)
    params = {"w1": jnp.asarray(rng.normal(0, 0.1, (d, h)), jnp.float32),
              "w2": jnp.asarray(rng.normal(0, 0.1, (h, out)), jnp.float32)}
    moments = jax.tree_util.tree_map(jnp.zeros_like, params)
    x = np.zeros((2, d), np.float32)
    y = np.zeros((2, out), np.float32)
    return jax.jit(train_step, donate_argnums=(0, 1)), \
        (params, moments, x, y)


def _exe_kv_extract():
    """The KV-block EXPORT gather (ISSUE 17): `MLPLMEngine._kv_gather`,
    the one compiled executable behind `extract_kv_blocks`. Pool x
    padded block-index vector -> contiguous slab; a disaggregated
    handoff is exactly one dispatch of this on the prefill tier. It
    must compile to a pure device copy: zero collectives on a single
    chip, zero host transfers — the payload crosses the host boundary
    AFTER this program returns, as one declared slab, never op-by-op
    from inside the executable."""
    import numpy as np

    from ..serving.engine import MLPLMEngine

    eng = MLPLMEngine(vocab_size=64, hidden=16, max_batch_size=4,
                      num_blocks=16, block_size=4, max_blocks_per_seq=4)
    idx = np.zeros((4,), np.int32)
    return eng._kv_gather, (eng.pools, idx)


def _exe_kv_inject():
    """The KV-block IMPORT scatter (ISSUE 17): `MLPLMEngine._kv_scatter`
    with a DONATED destination pool — `inject_kv_blocks` lands a
    migrated slab into freshly-allocated blocks in place (no second
    pool copy). Same boundary contract as the gather: the slab arrives
    as one declared argument; the compiled program itself moves no
    bytes to or from the host and speaks to no other chip."""
    import numpy as np

    from ..serving.engine import MLPLMEngine

    eng = MLPLMEngine(vocab_size=64, hidden=16, max_batch_size=4,
                      num_blocks=16, block_size=4, max_blocks_per_seq=4)
    idx = np.zeros((4,), np.int32)
    cache, = eng.pools
    slab = np.zeros((4,) + tuple(cache.shape[1:]), np.dtype(cache.dtype))
    return eng._kv_scatter, (eng.pools, idx, (slab,))


EXECUTABLES = {
    "ragged_decode": _exe_ragged_decode,
    "ragged_decode_quant": _exe_ragged_decode_quant,
    "ragged_decode_lora": _exe_ragged_decode_lora,
    "ragged_decode_tp": _exe_ragged_decode_tp,
    "quant_matmul": _exe_quant_matmul,
    "verify": _exe_verify,
    "verify_tp": _exe_verify_tp,
    "sampler": _exe_sampler,
    "train_step": _exe_train_step,
    "kv_extract": _exe_kv_extract,
    "kv_inject": _exe_kv_inject,
}


def lower_executable(name: str) -> str:
    """Optimized HLO text of one registered executable (compiled for the
    current backend)."""
    if name not in EXECUTABLES:
        raise ManifestError(f"unregistered executable {name!r} "
                            f"(registered: {sorted(EXECUTABLES)})")
    fn, args = EXECUTABLES[name]()
    compiled = fn.lower(*args).compile()
    return compiled.as_text()


def load_manifest(path: str) -> dict:
    try:
        with open(path) as f:
            data = json.load(f)
    except OSError as e:
        raise ManifestError(f"cannot read manifest {path}: {e}")
    except json.JSONDecodeError as e:
        raise ManifestError(f"manifest {path}: not JSON ({e})")
    if not isinstance(data, dict) or not isinstance(
            data.get("executables"), dict):
        raise ManifestError(f'manifest {path}: expected {{"executables": '
                            '{name: constraints}}')
    # validate entry shape AND value types UP FRONT: a manifest typo
    # must exit 2 before any executable is lowered, not surface as a
    # TypeError mid-audit
    for name, entry in data["executables"].items():
        if not isinstance(entry, dict):
            raise ManifestError(
                f"manifest {path}: executable {name!r} entry must be a "
                f"constraints object, got {type(entry).__name__}")
        unknown = set(entry) - _KNOWN_KEYS
        if unknown:
            raise ManifestError(
                f"manifest {path}: executable {name!r}: unknown key(s) "
                f"{sorted(unknown)} (known: {sorted(_KNOWN_KEYS)})")
        for key in ("host_transfer_ops_max", "collective_ops_max",
                    "collective_bytes_max"):
            if key in entry and not (isinstance(entry[key], int)
                                     and not isinstance(entry[key], bool)):
                raise ManifestError(
                    f"manifest {path}: executable {name!r}: {key} must "
                    f"be an integer, got {entry[key]!r}")
        kind_budget = entry.get("collective_budget")
        if kind_budget is not None and not (
                isinstance(kind_budget, dict)
                and all(isinstance(k, str) and isinstance(v, int)
                        and not isinstance(v, bool)
                        for k, v in kind_budget.items())):
            raise ManifestError(
                f"manifest {path}: executable {name!r}: "
                "collective_budget must map census kind -> integer, "
                f"got {kind_budget!r}")
        if "declared_dtype" in entry \
                and not isinstance(entry["declared_dtype"], str):
            raise ManifestError(
                f"manifest {path}: executable {name!r}: declared_dtype "
                f"must be a string, got {entry['declared_dtype']!r}")
        budget = entry.get("op_budget")
        if budget is not None and not (
                isinstance(budget, dict)
                and all(isinstance(k, str) and isinstance(v, int)
                        and not isinstance(v, bool)
                        for k, v in budget.items())):
            raise ManifestError(
                f"manifest {path}: executable {name!r}: op_budget must "
                f"map op name -> integer, got {budget!r}")
    return data


def run_audit(manifest_path: Optional[str] = None,
              only: Optional[List[str]] = None) -> dict:
    """Lower every manifest-listed executable and audit it. Returns
    ``{"ok", "platform", "executables": {name: {...actuals, findings}}}``.
    Raises ManifestError for config problems (unknown executable/key)."""
    import jax

    manifest = load_manifest(manifest_path or DEFAULT_MANIFEST)
    entries = manifest["executables"]
    names = list(entries) if only is None else list(only)
    report = {"ok": True, "platform": jax.default_backend(),
              "manifest": manifest_path or DEFAULT_MANIFEST,
              "executables": {}}
    for name in names:
        if name not in entries:
            raise ManifestError(f"executable {name!r} not in manifest")
        text = lower_executable(name)
        actuals, findings = audit_text(text, entries[name],
                                       platform=report["platform"])
        actuals["findings"] = findings
        report["executables"][name] = actuals
        if findings:
            report["ok"] = False
    return report
