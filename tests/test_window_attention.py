"""`paged_attention_ragged` with a sliding window (`ops/pallas/
paged_attention.py`): against its XLA ref, never reading a page the cache
manager has released behind the window, and at window 0 the bits it gave
before it took one."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework import flags
from paddle_tpu.ops.pallas import paged_attention as pk

L, NB, KVH, BS, D, H, LANES, WIDTH, T = 2, 80, 2, 4, 16, 8, 4, 16, 24


@pytest.fixture
def interpret():
    flags.set_flags({"pallas_interpret": True})
    yield
    flags.set_flags({"pallas_interpret": False})


def case(seed, q_lens, kv_lens):
    rng = np.random.default_rng(seed)
    kc = jnp.asarray(rng.normal(size=(L, NB, KVH, BS, D)), jnp.float32)
    vc = jnp.asarray(rng.normal(size=(L, NB, KVH, BS, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(T, H, D)), jnp.float32)
    tables = jnp.asarray(rng.permutation(NB)[:LANES * WIDTH]
                         .reshape(LANES, WIDTH), jnp.int32)
    kv = jnp.asarray(kv_lens, jnp.int32)
    lane, pos = pk.ragged_metadata(jnp.asarray(q_lens, jnp.int32), kv, T)
    return q, kc, vc, tables, kv, lane, pos


def kernel(*args, layer=1, window=0):
    return _KERNEL(*args, jnp.int32(layer), jnp.int32(window))


def xla_ref(*args, layer=1, window=0):
    return _REF(*args, jnp.int32(layer), jnp.int32(window))


# one compiled program each, whatever the batch, the layer and the window:
# all three are operands (the window rides scalar prefetch)
_KERNEL = jax.jit(lambda *a: pk.paged_attention_ragged(
    *a[:7], layer=a[7], window=a[8]))
_REF = jax.jit(lambda *a: pk.paged_attention_ragged_ref(
    *a[:7], layer=a[7], window=a[8]))

_PLAIN = jax.jit(lambda *a: pk.paged_attention_ragged(*a[:7], layer=a[7]))

BATCHES = {
    "decode": ([1, 1, 1, 1], [37, 9, 64, 20]),
    "chunks_and_an_empty_lane": ([9, 1, 0, 12], [30, 41, 0, 12]),
    "a_chunk_across_the_window": ([1, 10, 1, 1], [5, 17, 33, 2]),
}


@pytest.mark.parametrize("window", [1, 5, 8, 13, 100])
@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_kernel_with_a_window_against_its_ref(interpret, batch, window):
    args = case(1, *BATCHES[batch])
    got = kernel(*args, window=window)
    want = xla_ref(*args, window=window)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    # and the ref is the mask the equations give: a window at least as long
    # as every context is no window
    if window == 100:
        full = xla_ref(*args)
        np.testing.assert_allclose(want, full, atol=1e-6, rtol=0)
    else:
        assert np.abs(np.asarray(want) - np.asarray(xla_ref(*args))).max() \
            > 1e-3


@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_window_zero_gives_the_bits_of_no_window(interpret, batch):
    args = case(2, *BATCHES[batch])
    plain = np.asarray(_PLAIN(*args, jnp.int32(0)))
    for window in (0, 1 << 20):
        same = np.asarray(kernel(*args, layer=0, window=window))
        assert np.array_equal(plain.view(np.uint32), same.view(np.uint32))


@pytest.mark.parametrize("window", [3, 9, 16])
def test_a_released_page_is_never_read(interpret, window):
    """What the manager gives back behind the window (blocks wholly before
    `kv_len - q_len - window + 1`) is poisoned; the kernel's result does not
    change, so it fetched none of it, even into a page group it masks."""
    q_lens, kv_lens = BATCHES["chunks_and_an_empty_lane"]
    q, kc, vc, tables, kv, lane, pos = case(3, q_lens, kv_lens)
    want = xla_ref(q, kc, vc, tables, kv, lane, pos, window=window)
    kc, vc, t = np.array(kc), np.array(vc), np.asarray(tables)
    for b, (n, k) in enumerate(zip(q_lens, kv_lens)):
        for j in range(max(0, k - n - window + 1) // BS):
            kc[:, t[b, j]] = np.nan
            vc[:, t[b, j]] = np.nan
    got = kernel(q, jnp.asarray(kc), jnp.asarray(vc), tables, kv, lane, pos,
                 window=window)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
