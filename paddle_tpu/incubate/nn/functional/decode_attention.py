"""Decode-time attention functionals: masked MHA + block (paged) MHA.

Parity targets (reference):
- `python/paddle/incubate/nn/functional/masked_multihead_attention.py` —
  decode attention over a dense [2, B, H, max_seq, D] cache
  (kernel `paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu`).
- `python/paddle/incubate/nn/functional/block_multihead_attention.py:34` —
  attention over a paged block cache
  (kernel `paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu`).
- `python/paddle/incubate/nn/functional/blha_get_max_len.py`.

TPU design: the paged decode path runs the Pallas kernel in
`paddle_tpu.ops.pallas.paged_attention` (scalar-prefetch block-table gather +
online softmax); prefill runs flash/SDPA and scatters K/V into the block pool
with one XLA scatter. Quant/smooth arguments are accepted for API parity and
gated: int8/fp8 cache quantization is not implemented yet.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from ....core.tensor import Tensor
from ....ops._helpers import as_tensor

__all__ = ["masked_multihead_attention", "block_multihead_attention",
           "blha_get_max_len"]


def _arr(x):
    if x is None:
        return None
    return x._data if isinstance(x, Tensor) else x


def _wrap(a, like):
    return Tensor(a) if isinstance(like, Tensor) else a


def blha_get_max_len(seq_lens_encoder, seq_lens_decoder, batch_size=None):
    """Max encoder/decoder lengths this step (reference blha_get_max_len.py)."""
    import jax.numpy as jnp

    enc = _arr(as_tensor(seq_lens_encoder))
    dec = _arr(as_tensor(seq_lens_decoder))
    me = jnp.max(enc).reshape(1)
    md = jnp.max(dec).reshape(1)
    return Tensor(me), Tensor(md)


def masked_multihead_attention(x, cache_kv=None, bias=None, src_mask=None,
                               cum_offsets=None, sequence_lengths=None,
                               rotary_tensor=None, beam_cache_offset=None,
                               qkv_out_scale=None, out_shift=None,
                               out_smooth=None, seq_len=1, rotary_emb_dims=0,
                               use_neox_rotary_style=False,
                               compute_dtype="default", out_scale=-1,
                               quant_round_type=1, quant_max_bound=127.0,
                               quant_min_bound=-127.0):
    """Single-token decode attention over a dense KV cache.

    x: [B, 3*H*D] packed qkv for the newest token of each sequence.
    cache_kv: [2, B, H, max_seq, D]; sequence_lengths: [B] tokens already
    cached. Returns (out [B, H*D], updated cache) — reference contract.
    """
    import jax
    import jax.numpy as jnp

    if qkv_out_scale is not None or out_scale != -1:
        raise NotImplementedError(
            "int8 qkv/out quantization is not implemented on the TPU path")
    xq = as_tensor(x)
    xa = _arr(xq)
    cache = _arr(as_tensor(cache_kv))
    _, b, h, max_seq, d = cache.shape
    qkv = xa.reshape(b, 3, h, d)
    if bias is not None:
        qkv = qkv + _arr(as_tensor(bias)).reshape(1, 3, h, d)
    q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]          # [B, H, D]
    if sequence_lengths is None:
        raise ValueError("sequence_lengths is required")
    lens = _arr(as_tensor(sequence_lengths)).reshape(-1).astype(jnp.int32)

    if rotary_tensor is not None and rotary_emb_dims > 0:
        # rotary_tensor: [2, B, 1, max_seq, D] (cos;sin), reference layout.
        rot = _arr(as_tensor(rotary_tensor))
        cos = jnp.take_along_axis(rot[0][:, 0], lens[:, None, None], axis=1)
        sin = jnp.take_along_axis(rot[1][:, 0], lens[:, None, None], axis=1)
        cos = cos[:, None, 0, :]                        # [B, 1, D]
        sin = sin[:, None, 0, :]

        def rope(t):
            if use_neox_rotary_style:
                t1, t2 = jnp.split(t, 2, axis=-1)
                c, s = cos[..., :d // 2], sin[..., :d // 2]
                return jnp.concatenate([t1 * c - t2 * s, t2 * c + t1 * s], -1)
            te, to = t[..., 0::2], t[..., 1::2]
            c, s = cos[..., 0::2], sin[..., 0::2]
            r = jnp.stack([te * c - to * s, to * c + te * s], axis=-1)
            return r.reshape(t.shape)

        q, k = rope(q), rope(k)

    # write k/v at position lens[b] per sequence
    onehot = jax.nn.one_hot(lens, max_seq, dtype=cache.dtype)  # [B, max_seq]
    write = onehot[:, None, :, None]
    new_k = cache[0] * (1 - write) + k[:, :, None, :] * write
    new_v = cache[1] * (1 - write) + v[:, :, None, :] * write
    new_cache = jnp.stack([new_k, new_v])

    scores = jnp.einsum("bhd,bhsd->bhs", q.astype(jnp.float32),
                        new_k.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
    scores = scores / np.sqrt(d)
    pos = jnp.arange(max_seq)[None, :]
    mask = pos <= lens[:, None]                          # attend incl. new token
    scores = jnp.where(mask[:, None, :], scores, -1e30)
    if src_mask is not None:
        scores = scores + _arr(as_tensor(src_mask)).reshape(
            b, 1, -1)[:, :, :max_seq].astype(scores.dtype)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bhsd->bhd", probs, new_v.astype(jnp.float32))
    out = out.astype(xa.dtype).reshape(b, h * d)
    return _wrap(out, xq), _wrap(new_cache, xq)


def block_multihead_attention(
        qkv, key_cache, value_cache, seq_lens_encoder, seq_lens_decoder,
        seq_lens_this_time, padding_offsets=None, cum_offsets=None,
        cu_seqlens_q=None, cu_seqlens_k=None, block_tables=None,
        pre_key_cache=None, pre_value_cache=None, cache_k_quant_scales=None,
        cache_v_quant_scales=None, cache_k_dequant_scales=None,
        cache_v_dequant_scales=None, qkv_out_scale=None, qkv_bias=None,
        out_shift=None, out_smooth=None, max_enc_len_this_time=None,
        max_dec_len_this_time=None, rope_emb=None, mask=None, tgt_mask=None,
        max_seq_len=-1, block_size=64, use_neox_style=False,
        use_dynamic_cachekv_quant=False, quant_round_type=1,
        quant_max_bound=127.0, quant_min_bound=-127.0, out_scale=-1,
        compute_dtype="default", num_heads=None, num_kv_heads=None):
    """Paged-cache attention (prefill + decode) — reference
    `block_multihead_attention.py:34`.

    qkv: [token_num, (H + 2*KVH) * D] packed ragged tokens (cu_seqlens_q gives
    per-sequence offsets). key/value_cache: [max_block_num, KVH, block_size, D].
    A call must be pure-prefill (all seq_lens_decoder == 0) or pure-decode
    (all seq_lens_this_time == 1); serving engines batch the two phases
    separately, matching the reference kernel's enc/dec split.

    Returns (fmha_out [token_num, H*D], qkv_out, key_cache, value_cache).
    """
    import jax.numpy as jnp

    from ....ops.pallas import paged_attention as pk

    if use_dynamic_cachekv_quant or cache_k_quant_scales is not None:
        raise NotImplementedError("cache-kv quantization not implemented")
    qkv_t = as_tensor(qkv)
    qkva = _arr(qkv_t)
    kc = _arr(as_tensor(key_cache))
    vc = _arr(as_tensor(value_cache))
    tables = _arr(as_tensor(block_tables)).astype(jnp.int32)
    enc = np.asarray(_arr(as_tensor(seq_lens_encoder))).reshape(-1)
    dec = np.asarray(_arr(as_tensor(seq_lens_decoder))).reshape(-1)
    this_time = np.asarray(_arr(as_tensor(seq_lens_this_time))).reshape(-1)
    b = enc.shape[0]
    nb, kv_h, bs, d = kc.shape
    if bs != block_size and block_size != 64:
        raise ValueError("block_size mismatch with cache shape")
    total = qkva.shape[0]
    width = qkva.shape[1] // d
    if num_kv_heads is not None:
        h = num_heads if num_heads is not None else width - 2 * num_kv_heads
        assert h + 2 * num_kv_heads == width
        kv_h_q = num_kv_heads
    else:
        kv_h_q = kv_h
        h = width - 2 * kv_h
    if qkv_bias is not None:
        qkva = qkva + _arr(as_tensor(qkv_bias)).reshape(1, -1)
    qkvr = qkva.reshape(total, width, d)
    q = qkvr[:, :h]
    k = qkvr[:, h:h + kv_h_q]
    v = qkvr[:, h + kv_h_q:]

    if rope_emb is not None:
        # rope_emb: [2, B, max_seq, 1, D/2] (cos;sin) — applied at each
        # token's absolute position (decoder len + offset within this step).
        rot = _arr(as_tensor(rope_emb))
        seq_ids = np.repeat(np.arange(b), this_time)
        pos_in = np.concatenate([np.arange(n) for n in this_time]) \
            if total else np.zeros((0,), np.int64)
        abs_pos = jnp.asarray(dec[seq_ids] + pos_in, jnp.int32)
        cos = rot[0][jnp.asarray(seq_ids), abs_pos, 0]   # [T, D/2]
        sin = rot[1][jnp.asarray(seq_ids), abs_pos, 0]

        def rope_fn(t):
            c = cos[:, None, :].astype(t.dtype)
            s = sin[:, None, :].astype(t.dtype)
            if use_neox_style:
                t1, t2 = jnp.split(t, 2, axis=-1)
                return jnp.concatenate([t1 * c - t2 * s, t2 * c + t1 * s], -1)
            te, to = t[..., 0::2], t[..., 1::2]
            r = jnp.stack([te * c - to * s, to * c + te * s], axis=-1)
            return r.reshape(t.shape)

        q, k = rope_fn(q), rope_fn(k)

    is_decode = bool((dec > 0).any()) or bool((this_time == 1).all()
                                              and (enc == 0).all())
    if bool((enc > 0).any()) and bool((dec > 0).any()):
        raise NotImplementedError(
            "mixed prefill+decode batches: split the call per phase "
            "(the reference kernel also runs enc and dec token groups "
            "through separate paths)")

    if is_decode:
        # one token per sequence: q is [B, H, D]
        start = jnp.asarray(dec, jnp.int32)
        kc, vc = pk.write_kv_to_cache(k.reshape(b, 1, kv_h_q, d),
                                      v.reshape(b, 1, kv_h_q, d),
                                      kc, vc, tables, start)
        ctx = jnp.asarray(dec + 1, jnp.int32)
        qd = q.reshape(b, h, d)
        if pk.ragged_supported(qd.shape, qd.dtype, kc.shape, kc.dtype,
                               tables.shape[1]):
            out = pk.paged_attention(qd, kc, vc, tables, ctx)
        else:
            out = pk.paged_attention_ref(qd, kc, vc, tables, ctx)
        out = out.reshape(total, h * d)
    else:
        # prefill: per-sequence causal attention + cache write
        outs = []
        off = 0
        for i in range(b):
            n = int(this_time[i])
            qi = q[off:off + n][None]                   # [1, S, H, D]
            ki = k[off:off + n][None]
            vi = v[off:off + n][None]
            kc, vc = pk.write_kv_to_cache(
                ki, vi, kc, vc, tables[i:i + 1],
                jnp.zeros((1,), jnp.int32))
            if kv_h_q != h:
                rep = h // kv_h_q
                ki = jnp.repeat(ki, rep, axis=2)
                vi = jnp.repeat(vi, rep, axis=2)
            from ....nn.functional.attention import _sdpa_fn

            oi = _sdpa_fn(qi, ki, vi, None, True, None, False)
            outs.append(oi[0].reshape(n, h * d))
            off += n
        out = jnp.concatenate(outs, axis=0)
    out = out.astype(qkva.dtype)
    return (_wrap(out, qkv_t), _wrap(qkva, qkv_t), _wrap(kc, qkv_t),
            _wrap(vc, qkv_t))
