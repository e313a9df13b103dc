"""The two power-retention kernels (`ops/pallas/power_retention.py`) through
the Pallas interpreter against their plain `jnp` twins, on a packed step's
shapes: decode lanes, chunk lanes over one and over several row blocks,
fresh and carried states, dead lanes whose state must come back untouched.
float32 both sides: they differ in the order of sums (readings to 2e-5 on
values of size 1 to 20)."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.framework import flags
from paddle_tpu.ops.pallas import power_retention as pr

L, SLOTS, KV, G, D = 2, 6, 2, 2, 16
O = pr.n_offsets(D)
ATOL = 1e-4


@pytest.fixture(autouse=True)
def interpret():
    flags.set_flags({"pallas_interpret": True})
    yield
    flags.set_flags({"pallas_interpret": False})


def state(rng):
    return (jnp.asarray(rng.normal(size=(L, SLOTS, KV, O, D, D)), jnp.float32),
            jnp.asarray(np.abs(rng.normal(size=(L, SLOTS, KV, O, D))) + 1,
                        jnp.float32))


def rows(rng, n):
    return (jnp.asarray(rng.normal(size=(n, KV, G, D)), jnp.float32) * 0.4,
            jnp.asarray(rng.normal(size=(n, KV, D)), jnp.float32),
            jnp.asarray(rng.normal(size=(n, KV, D)), jnp.float32),
            jnp.asarray(-np.abs(rng.normal(size=(n, KV))) * 0.05, jnp.float32))


def close(got, want):
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=ATOL)


def untouched(new, old, layer, slots):
    """Every slot but `slots` of `layer`, and every other layer, bit for bit."""
    keep = np.ones((L, SLOTS), bool)
    keep[layer, list(slots)] = False
    for n, o in zip(new, old):
        np.testing.assert_array_equal(np.asarray(n)[keep], np.asarray(o)[keep])


def test_the_gate_says_yes_here():
    assert pr.retention_supported(D, jnp.float32)
    assert not pr.retention_supported(D, jnp.float16)
    assert pr.feature_dim(128) == 8320


@pytest.mark.parametrize("live,fresh", [
    ([1, 1, 1, 1], [0, 0, 0, 0]), ([1, 0, 1, 0], [1, 0, 0, 0]),
    ([0, 0, 1, 1], [0, 0, 1, 1]), ([0, 1, 0, 0], [0, 0, 0, 0]),
    ([0, 0, 0, 0], [0, 0, 0, 0])],
    ids=["all", "alternate", "late", "one", "none"])
@pytest.mark.parametrize("layer", [0, 1])
def test_update_kernel_is_its_twin(live, fresh, layer):
    rng = np.random.default_rng(7)
    S, z = state(rng)
    kw = dict(layer=layer, slot=jnp.asarray([4, 1, 0, 3], jnp.int32),
              live=jnp.asarray(live, bool), fresh=jnp.asarray(fresh, bool),
              eps=1e-6)
    want = pr.power_retention_update_ref(*rows(np.random.default_rng(1), 4),
                                         S, z, **kw)
    got = pr.power_retention_update(*rows(np.random.default_rng(1), 4), S, z,
                                    **kw)
    close(got, want)
    touched = [s for s, on in zip([4, 1, 0, 3], live) if on]
    untouched(got[1:], (S, z), layer, touched)
    dead = ~np.asarray(live, bool)
    assert not np.asarray(got[0])[dead].any()


# lanes' rows in a packed buffer of 40: q_lens, and which lanes are chunks
STEPS = {
    "one_chunk": ([1, 10, 0, 1], [0, 1, 0, 0], [0, 1, 0, 0]),
    "two_chunks": ([7, 1, 12, 0], [1, 0, 1, 0], [0, 0, 1, 0]),
    "carried": ([1, 1, 30, 1], [0, 0, 1, 0], [0, 0, 0, 0]),
    "first_lane_late": ([0, 0, 0, 5], [0, 0, 0, 1], [0, 0, 0, 1]),
    "none": ([1, 1, 1, 1], [0, 0, 0, 0], [0, 0, 0, 0]),
}


@pytest.mark.parametrize("row_block", [8, 128], ids=["blocks_of_8", "one_block"])
@pytest.mark.parametrize("step", sorted(STEPS))
def test_chunk_kernel_is_its_twin(step, row_block):
    q_lens, live, fresh = STEPS[step]
    rng = np.random.default_rng(11)
    S, z = state(rng)
    lane_of = np.full((40,), -1, np.int32)
    lane_of[:sum(q_lens)] = np.repeat(np.arange(4), q_lens)
    slots = [2, 5, 1, 0]
    kw = dict(layer=1, slot=jnp.asarray(slots, jnp.int32),
              live=jnp.asarray(live, bool), fresh=jnp.asarray(fresh, bool),
              tok_lane=jnp.asarray(lane_of), eps=1e-6)
    packed = rows(np.random.default_rng(2), 40)
    want = pr.power_retention_chunk_ref(*packed, S, z, **kw)
    got = pr.power_retention_chunk(*packed, S, z, row_block=row_block, **kw)
    close(got, want)
    untouched(got[1:], (S, z), 1, [s for s, on in zip(slots, live) if on])
    mine = np.isin(lane_of, [b for b, on in enumerate(live) if on])
    assert not np.asarray(got[0])[~mine].any()
    if any(live):
        assert np.abs(np.asarray(got[0])[mine]).max() > 1e-3


def test_a_chunk_in_two_steps_is_the_chunk_in_one():
    """The state a chunk leaves is the state the next chunk starts from:
    30 rows at once against 18 then 12 through the kernel."""
    rng = np.random.default_rng(3)
    S, z = state(rng)
    q, k, v, a = rows(np.random.default_rng(4), 30)
    kw = dict(layer=0, slot=jnp.asarray([3], jnp.int32),
              live=jnp.asarray([True]), eps=1e-6, row_block=8)
    lane = lambda n: jnp.zeros((n,), jnp.int32)             # noqa: E731
    y, S1, z1 = pr.power_retention_chunk(
        q, k, v, a, S, z, fresh=jnp.asarray([True]), tok_lane=lane(30), **kw)
    ya, Sa, za = pr.power_retention_chunk(
        q[:18], k[:18], v[:18], a[:18], S, z, fresh=jnp.asarray([True]),
        tok_lane=lane(18), **kw)
    yb, Sb, zb = pr.power_retention_chunk(
        q[18:], k[18:], v[18:], a[18:], Sa, za, fresh=jnp.asarray([False]),
        tok_lane=lane(12), **kw)
    close((jnp.concatenate([ya, yb]), Sb, zb), (y, S1, z1))
