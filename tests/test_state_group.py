"""A STATE group of the cache manager (`BlockCacheManager.state_group`): one
slot a sequence for its whole life, however long it grows; a table that is
the slot; a length bounded by the position table alone; and no way to trim.
Host bookkeeping only: no device, milliseconds."""
import numpy as np
import pytest

from paddle_tpu.inference.cache import (BlockCacheManager, KVCacheExhausted,
                                        SequenceTooLong, StateNotTrimmable)

SLOTS, CONTEXT = 4, 1000


@pytest.fixture
def mgr():
    return BlockCacheManager.state_group(SLOTS, CONTEXT)


def test_it_is_slots_plus_a_guard(mgr):
    assert mgr.state and mgr.n_groups == 1 and mgr.group_names == ("state",)
    assert mgr.num_blocks == SLOTS + 1 == mgr.free_blocks
    assert mgr.table_width == 1 and mgr.max_blocks_per_seq == 1
    assert not BlockCacheManager(8, 16, 4).state


@pytest.mark.parametrize("tokens", [0, 1, 17, CONTEXT])
def test_a_sequence_holds_one_slot_whatever_its_length(mgr, tokens):
    assert mgr.blocks_needed(tokens) == 1
    (slot,) = mgr.allocate(7, tokens)
    assert mgr.seq_blocks(7) == 1 and mgr.blocks_of(7) == (slot,)
    assert mgr.free_blocks == SLOTS


def test_growth_takes_nothing_and_free_gives_the_slot_back(mgr):
    mgr.allocate(1, 0)
    before = mgr.free_blocks
    for n in (1, 64, 500):
        mgr.append_tokens(1, n)
    assert mgr.seq_len(1) == 565 and mgr.free_blocks == before
    assert mgr.seq_blocks(1) == 1
    mgr.free(1)
    assert mgr.free_blocks == SLOTS + 1 and mgr.seq_blocks(1) == 0
    mgr.check_consistency()


def test_the_table_is_the_slot(mgr):
    mgr.allocate(-1, 1)                        # a scheduler's guard
    slots = [mgr.allocate(i, 3)[0] for i in range(3)]
    table = mgr.block_table_array([0, 1, 2, -1])
    assert table.shape == (4, 1) and table.dtype == np.int32
    assert table[:3, 0].tolist() == slots and len(set(table[:, 0])) == 4


def test_too_long_is_the_position_table_s_alone(mgr):
    with pytest.raises(SequenceTooLong):
        mgr.allocate(1, CONTEXT + 1)
    mgr.allocate(1, CONTEXT - 1)
    mgr.append_tokens(1, 1)
    with pytest.raises(SequenceTooLong):
        mgr.append_tokens(1, 1)
    assert mgr.seq_len(1) == CONTEXT           # all or nothing


def test_exhaustion_is_slots(mgr):
    for i in range(SLOTS + 1):
        mgr.allocate(i, 10)
    assert not mgr.can_allocate(1)
    with pytest.raises(KVCacheExhausted) as e:
        mgr.allocate(99, 1)
    assert (e.value.need, e.value.free, e.value.total) == (1, 0, SLOTS + 1)
    assert e.value.group == "state"


def test_utilization_counts_sequences_not_the_guard(mgr):
    mgr.allocate(-1, 1)
    assert mgr.utilization() == 0.0
    mgr.allocate(0, 900)
    mgr.allocate(1, 1)
    assert mgr.utilization() == pytest.approx(2 / SLOTS)
    assert mgr.free_blocks_of(0) == SLOTS - 2
    mgr.set_kv_geometry(1000, 32)
    frag = mgr.fragmentation()
    assert frag["leased_bytes"] == 2000 and frag["guard_blocks"] == 1
    assert frag["kv_bits"] == 32 and frag["tokens"] == 901


@pytest.mark.parametrize("to", [0, 1, 11])
def test_trim_below_its_length_raises_by_name(mgr, to):
    mgr.allocate(3, 12)
    with pytest.raises(StateNotTrimmable, match="state group 'state'"):
        mgr.trim(3, to)
    assert mgr.seq_len(3) == 12
    mgr.trim(3, 12)                            # to its own length: nothing
    assert mgr.seq_len(3) == 12 and mgr.seq_blocks(3) == 1


def test_unappend_is_bookkeeping_for_what_never_reached_the_device(mgr):
    mgr.allocate(3, 12)
    mgr.append_tokens(3, 5)
    mgr.unappend(3, 12)
    assert mgr.seq_len(3) == 12
    with pytest.raises(ValueError):
        mgr.unappend(3, 13)
    # over block groups it is `trim`
    blocks = BlockCacheManager(8, 4, 4)
    blocks.allocate(0, 4)
    blocks.append_tokens(0, 6)
    blocks.unappend(0, 4)
    assert blocks.seq_len(0) == 4 and blocks.seq_blocks(0) == 1


def test_a_slot_is_never_shared(mgr):
    (slot,) = mgr.allocate(0, 4)
    with pytest.raises(ValueError, match="state group"):
        mgr.adopt(1, [slot], 4)
    with pytest.raises(ValueError, match="already allocated"):
        mgr.allocate(0, 1)
    mgr.check_consistency()


def test_consistency_sees_a_slot_leased_twice(mgr):
    (slot,) = mgr.allocate(0, 4)
    mgr._free.append(slot)
    with pytest.raises(AssertionError):
        mgr.check_consistency()
