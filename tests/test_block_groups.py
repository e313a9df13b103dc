"""`BlockCacheManager` over several groups of layers (`inference/cache.py`):
a further group has a block-id space of its own, a windowed one gives back
the blocks that lie wholly behind the window of the COMMITTED length, and a
manager of one group is what it always was."""
import numpy as np
import pytest

from paddle_tpu.inference.cache import (BlockCacheManager, KVCacheExhausted,
                                        SequenceTooLong)

BS, W = 4, 10


def manager(num_blocks=64, window_blocks=24, width=32, window=W):
    return BlockCacheManager(num_blocks, BS, width, name="full",
                             further_groups=[("window", window_blocks, window)])


def live(mgr, sid, group=1):
    """Logical indices of the blocks `sid` still holds in `group`."""
    row = mgr.block_table_array([sid], pad=-7)[0]
    w = mgr.max_blocks_per_seq
    return [i for i, b in enumerate(row[group * w:(group + 1) * w]) if b != -7]


def test_one_group_is_the_manager_there_was():
    mgr = BlockCacheManager(9, BS, 8)
    assert (mgr.n_groups, mgr.table_width, mgr.group_names) == (1, 8, ("kv",))
    mgr.allocate(0, 5)
    mgr.append_tokens(0, 6)
    assert mgr.block_table_array([0], pad=3).shape == (1, 8)
    assert mgr.group_window() is None and mgr.num_blocks_of() == 9
    assert mgr.free_blocks_of() == mgr.free_blocks == 6
    with pytest.raises(KVCacheExhausted, match=r"^KV cache pool exhausted"):
        mgr.allocate(1, 7 * BS)
    with pytest.raises(SequenceTooLong, match=r"needs 9 blocks > max"):
        mgr.allocate(2, 9 * BS)
    mgr.check_consistency()


@pytest.mark.parametrize("steps", [[1] * 40, [7, 7, 7, 1, 1, 9, 3], [16, 16, 5],
                                   [3, 1, 1, 12, 1, 1, 1, 1, 20]])
def test_releases_exactly_what_lies_wholly_behind_the_committed_window(steps):
    mgr = manager()
    mgr.allocate(0, 0)
    committed = 0
    for n in steps:
        mgr.append_tokens(0, n)
        # the next query that can ever be asked sits at `committed` (a trim
        # goes back no further) and sees positions > committed - W
        first = max(0, committed - W + 1) // BS
        last = (committed + n - 1) // BS
        assert live(mgr, 0) == list(range(first, last + 1))
        assert live(mgr, 0, group=0) == list(range(0, last + 1))
        assert mgr.seq_blocks(0, 1) == last + 1 - first
        assert mgr.seq_blocks(0, 1) <= mgr.blocks_needed(10 ** 6, 1, step=n)
        committed += n
        mgr.check_consistency()
    assert mgr.blocks_released(1) == max(0, committed - n - W + 1) // BS
    mgr.free(0)
    assert mgr.free_blocks_of(1) == 24 and mgr.free_blocks == 64
    mgr.check_consistency()


def test_a_speculative_trim_never_needs_a_released_block():
    mgr = manager()
    mgr.allocate(0, 0)
    mgr.append_tokens(0, 23)
    for _ in range(30):
        before = mgr.seq_len(0)
        mgr.append_tokens(0, 1 + 4)                 # pending + 4 drafts
        for accepted in (4, 2, 0):
            # every length from the committed one up is still reachable
            keep = before + 1 + accepted
            first = max(0, keep - W + 1) // BS
            assert first >= min(live(mgr, 0))
        mgr.trim(0, before + 1 + 1)                 # one draft accepted
        mgr.check_consistency()
    # back to the committed length itself (a failed round's rollback)
    before = mgr.seq_len(0)
    mgr.append_tokens(0, 8)
    mgr.trim(0, before)
    assert min(live(mgr, 0)) <= max(0, before - W + 1) // BS
    # behind what is committed there is nothing to go back to
    with pytest.raises(ValueError, match="released behind its window"):
        mgr.trim(0, 3)
    assert mgr.seq_len(0) == before, "a refused trim changes nothing"
    mgr.check_consistency()


def test_all_groups_move_or_none():
    mgr = manager(num_blocks=64, window_blocks=5)
    mgr.allocate(0, 0)
    mgr.append_tokens(0, 16)                        # 4 of the 5 window blocks
    mgr.allocate(1, 0)                              # the fifth
    with pytest.raises(KVCacheExhausted, match="group 'window'") as e:
        mgr.append_tokens(1, 6)
    assert e.value.group == "window" and e.value.free == 0
    assert mgr.seq_len(1) == 0 and mgr.seq_blocks(1) == 1
    with pytest.raises(KVCacheExhausted, match="group 'window'"):
        mgr.allocate(2, 1)
    assert mgr.seq_blocks(2) == 0 and mgr.free_blocks == 64 - 4 - 1
    # the full group running out names itself too
    small = manager(num_blocks=3, window_blocks=24)
    small.allocate(0, 8)
    with pytest.raises(KVCacheExhausted, match="group 'full'"):
        small.append_tokens(0, 8)
    assert small.seq_blocks(0, 1) == 2
    with pytest.raises(SequenceTooLong, match="group 'full'"):
        manager(width=2).allocate(0, 3 * BS)
    with pytest.raises(ValueError, match="adopt"):
        mgr.adopt(9, [0], 1)
    mgr.check_consistency()
    small.check_consistency()


def test_tables_side_by_side_and_pads():
    mgr = manager(width=8)
    mgr.allocate(-1, 1)                             # a guard sequence
    mgr.allocate(5, 0)
    mgr.append_tokens(5, 9)
    mgr.append_tokens(5, 9)          # committed 9: position 0 is still seen
    mgr.append_tokens(5, 1)          # committed 18: blocks 0 and 1 go
    t = mgr.block_table_array([5, -1], pad=99)
    assert t.shape == (2, 16) and mgr.table_width == 16
    assert (t[0, :5] != 99).all() and (t[0, 5:8] == 99).all()
    assert (t[0, 8:10] == 99).all() and (t[0, 10:13] != 99).all() \
        and (t[0, 13:] == 99).all()
    assert set(t[0, :5]) == set(mgr.blocks_of(5))
    assert list(t[0, 10:13]) == list(mgr.blocks_of(5, 1))
    assert t[1, 0] == mgr.blocks_of(-1)[0] and t[1, 8] == mgr.blocks_of(-1, 1)[0]
    assert mgr.utilization(1) == pytest.approx(3 / 23)
    frag = mgr.fragmentation(1)
    assert (frag["group"], frag["leased_blocks"], frag["released_blocks"]) \
        == ("window", 4, 2)


def test_consistency_under_a_random_run():
    rng = np.random.default_rng(38)
    mgr = manager(num_blocks=40, window_blocks=20, width=16)
    alive, committed = {}, {}
    for _ in range(1500):
        op = rng.integers(0, 4)
        try:
            if op == 0 and len(alive) < 6:
                sid = int(rng.integers(0, 1000))
                if sid not in alive:
                    mgr.allocate(sid, int(rng.integers(0, 9)))
                    alive[sid] = committed[sid] = mgr.seq_len(sid)
            elif op == 1 and alive:
                sid = int(rng.choice(list(alive)))
                committed[sid] = mgr.seq_len(sid)
                mgr.append_tokens(sid, int(rng.integers(1, 10)))
            elif op == 2 and alive:
                sid = int(rng.choice(list(alive)))
                mgr.trim(sid, int(rng.integers(committed[sid],
                                               mgr.seq_len(sid) + 1)))
            elif op == 3 and alive:
                sid = int(rng.choice(list(alive)))
                mgr.free(sid)
                del alive[sid], committed[sid]
        except (KVCacheExhausted, SequenceTooLong):
            pass
        mgr.check_consistency()
        for sid in alive:
            first = max(0, committed[sid] - W + 1) // BS
            assert not live(mgr, sid) or min(live(mgr, sid)) <= first
    for sid in list(alive):
        mgr.free(sid)
    assert mgr.free_blocks == 40 and mgr.free_blocks_of(1) == 20
