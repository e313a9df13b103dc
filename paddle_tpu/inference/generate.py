"""`generate()` for any `EngineCore`: one host loop over `ragged_step` and
the engine's `BlockCacheManager`.

The prompt goes through ONE ragged step (`q_len = S` a lane), every later
token through a `q_len = 1` step: two compiled shapes a (batch, prompt
length), whatever the engine — full-precision or int8 KV, LoRA-wrapped,
TP-sharded, Llama, MLP or DeepSeek-V3. Each engine class binds it as its
`generate` method.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

__all__ = ["GenerationConfig", "generate"]


class GenerationConfig:
    def __init__(self, max_new_tokens: int = 32, do_sample: bool = False,
                 temperature: float = 1.0, top_p: float = 1.0,
                 top_k: int = 0, eos_token_id: Optional[int] = None,
                 seed: int = 0):
        self.max_new_tokens = max_new_tokens
        self.do_sample = do_sample
        self.temperature = temperature
        self.top_p = top_p
        self.top_k = top_k
        self.eos_token_id = eos_token_id
        self.seed = seed


def generate(engine, input_ids, generation_config: GenerationConfig = None,
             **kw) -> np.ndarray:
    """Greedy/sampling generation on an idle engine. input_ids: [B, S]
    (equal-length prompts, B <= `engine.max_batch_size`; ragged batches
    and live traffic go through the scheduler). Returns
    [B, S + max_new_tokens], fewer columns when every row hit
    `eos_token_id` first. Sequences 0..B-1 of the engine's manager are
    leased for the call and freed when it ends, however it ends."""
    gc = generation_config or GenerationConfig(**kw)
    ids = np.asarray(input_ids, np.int32)
    if ids.ndim == 1:
        ids = ids[None]
    b, s = ids.shape
    if b > engine.max_batch_size:
        raise ValueError(f"{b} prompts on an engine of "
                         f"{engine.max_batch_size} lanes")
    mgr = engine.manager
    seq_ids = list(range(b))
    rng = np.random.default_rng(gc.seed)
    out = [ids]
    done = np.zeros(b, bool)
    try:
        for sid in seq_ids:
            mgr.allocate(sid, s)
        lens = np.full((b,), s, np.int32)
        logits = engine.ragged_step(
            ids.reshape(b * s), lens, lens, mgr.block_table_array(seq_ids))
        # each lane's last row, taken before the rows cross to the host
        last = _pick(np.asarray(logits[s - 1::s]), gc, rng)
        ones = np.ones((b,), np.int32)
        for _ in range(gc.max_new_tokens):
            out.append(last[:, None])
            if gc.eos_token_id is not None:
                done |= last == gc.eos_token_id
                if done.all():
                    break
            for sid in seq_ids:
                mgr.append_token(sid)
            lens = np.asarray([mgr.seq_len(sid) for sid in seq_ids],
                              np.int32)
            logits = np.asarray(engine.ragged_step(
                last, ones, lens, mgr.block_table_array(seq_ids)))
            last = _pick(logits, gc, rng)
    finally:
        for sid in seq_ids:
            if mgr.blocks_of(sid):
                mgr.free(sid)
    return np.concatenate(out, axis=1)


def _pick(logits: np.ndarray, gc: GenerationConfig, rng) -> np.ndarray:
    if not gc.do_sample:
        return np.argmax(logits, axis=-1).astype(np.int32)
    x = logits.astype(np.float64) / max(gc.temperature, 1e-6)
    if gc.top_k:
        kth = np.partition(x, -gc.top_k, axis=-1)[:, -gc.top_k][:, None]
        x = np.where(x < kth, -np.inf, x)
    p = np.exp(x - x.max(axis=-1, keepdims=True))
    p /= p.sum(axis=-1, keepdims=True)
    if gc.top_p < 1.0:
        order = np.argsort(-p, axis=-1)
        ps = np.take_along_axis(p, order, -1)
        cum = np.cumsum(ps, axis=-1)
        keep = cum - ps < gc.top_p   # always keep the top token
        ps = np.where(keep, ps, 0.0)
        ps /= ps.sum(axis=-1, keepdims=True)
        picked = np.stack([rng.choice(ps.shape[1], p=ps[i])
                           for i in range(ps.shape[0])])
        return np.take_along_axis(order, picked[:, None], -1)[:, 0].astype(
            np.int32)
    return np.stack([rng.choice(p.shape[1], p=p[i])
                     for i in range(p.shape[0])]).astype(np.int32)
