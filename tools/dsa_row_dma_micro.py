"""The reading PERF.md section 7 (r) lacks: how fast a Pallas kernel can
bring single cache rows out of a paged pool by DMA, which decides whether
the GLM step's gather of selected rows (XLA's, 12.7 ns a row: 0.83 ms for
65,536) can be predicted as a kernel. A kernel alone, no cell's code:

    python3 tools/dsa_row_dma_micro.py [--blocks 11000] [--seed 48]

32 grid steps; each brings 2,048 rows from scalar `block * 64 + offset` ids
(a step's ids in SMEM) out of a pool in HBM into VMEM, with 1, 4 or 16 DMAs
in flight, and the rows leave as the step's output block, so the result is
checked against `jnp.take`. What Mosaic on the v5e lets a DMA address
decides the pool's shape: a slice of an HBM array must be whole tiles, so
one row of `[NB, 64, 640]` bfloat16 (half of a packed sublane) or of `[NB,
64, 320]` uint32 (one sublane of eight) is refused, and so is a row of 320
words (not whole lanes). The pool here is `[NB * 64, 1, 384]` uint32: a row
its own `(1, 128)` tiles, 1,536 B (a latent row of 640 bfloat16 is 1,280 B,
padded to whole lanes of words). A kernel that gathers rows needs the latent
pool laid out so. Prints one JSON line, `ROW_DMA {...}`, ns a row by depth
beside XLA's `take` of the same rows from the same pool. It measures only on
a TPU.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ROWS, STEPS, BS, WORDS = 2048, 32, 64, 384


def _kernel(ids_ref, pool_hbm, o_ref, sem, *, depth):
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def copy(j):
        return pltpu.make_async_copy(pool_hbm.at[ids_ref[0, 0, j]],
                                     o_ref.at[j], sem.at[j % depth])

    def row(j, _):
        @pl.when(j >= depth)
        def _():
            copy(j - depth).wait()
        copy(j).start()

    jax.lax.fori_loop(0, ROWS, row, None)
    jax.lax.fori_loop(ROWS - depth, ROWS, lambda j, _: copy(j).wait(), None)


def gather_rows(pool, ids, depth: int):
    """`pool [NB * 64, 1, 320]` uint32, `ids [32, 2048]` int32 (`block * 64
    + offset`) -> `[32 * 2048, 1, 320]`, `depth` row DMAs in flight."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from paddle_tpu.ops.pallas import _support

    return _support.pallas_call(
        functools.partial(_kernel, depth=depth),
        grid=(STEPS,),
        in_specs=[pl.BlockSpec((1, 1, ROWS), lambda i: (i, 0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((ROWS, 1, WORDS), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((STEPS * ROWS, 1, WORDS), jnp.uint32),
        scratch_shapes=[pltpu.SemaphoreType.DMA((depth,))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name=f"dsa_row_dma_{depth}",
        interpret=_support.interpret_mode(),
    )(ids[:, None, :], pool)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", type=int, default=11000)
    ap.add_argument("--seed", type=int, default=48)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    if jax.default_backend() != "tpu":
        sys.exit("dsa_row_dma_micro: a DMA rate is read on a TPU only")
    rng = np.random.default_rng(args.seed)
    pool = jax.random.bits(jax.random.key(args.seed),
                           (args.blocks * BS, 1, WORDS), jnp.uint32)
    # a lane's selection: 2,048 of its 57,344 positions in position order,
    # its 896 blocks anywhere in the pool
    tables = rng.integers(0, args.blocks, (STEPS, 896))
    picks = np.sort(np.stack([rng.choice(896 * BS, ROWS, replace=False)
                              for _ in range(STEPS)]), axis=1)
    ids = jnp.asarray(np.take_along_axis(tables, picks // BS, 1) * BS
                      + picks % BS, jnp.int32)

    def bench(fn, n=20):
        out = fn(pool, ids)
        jax.block_until_ready(out)
        began = time.perf_counter()
        for _ in range(n):
            out = fn(pool, ids)
        jax.block_until_ready(out)
        return (time.perf_counter() - began) / n, out

    take = jax.jit(lambda p, i: jnp.take(p, i.reshape(-1), axis=0))
    s, want = bench(take)
    out = {"rows": STEPS * ROWS, "row_bytes": WORDS * 4,
           "xla_take_ns_per_row": round(s / (STEPS * ROWS) * 1e9, 2)}
    for depth in (1, 4, 16):
        s, got = bench(jax.jit(functools.partial(gather_rows, depth=depth)))
        out[f"dma_depth_{depth}_ns_per_row"] = round(
            s / (STEPS * ROWS) * 1e9, 2)
        out[f"dma_depth_{depth}_same"] = bool((got == want).all())
        print(f"depth {depth}: {out[f'dma_depth_{depth}_ns_per_row']} ns a "
              f"row, same {out[f'dma_depth_{depth}_same']}", flush=True)
    print("ROW_DMA " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
