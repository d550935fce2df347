"""MITHRIL core semantics vs the paper's sequential algorithm."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (EMPTY, MithrilConfig, associations_dense, init,
                        lookup, mine, mine_reference_sequential, record)


def small_cfg(**kw):
    base = dict(min_support=2, max_support=4, lookahead=10, rec_buckets=64,
                rec_ways=4, mine_rows=8, pf_buckets=64, pf_ways=4)
    base.update(kw)
    return MithrilConfig(**base)


def run_trace(cfg, blocks):
    st = init(cfg)
    rec = jax.jit(functools.partial(record, cfg))
    for b in blocks:
        st = rec(st, jnp.int32(b))
    return st


class TestDenseVsSequential:
    @pytest.mark.parametrize("seed", range(5))
    def test_random_tables(self, seed):
        rng = np.random.default_rng(seed)
        n, s, r_, s_max, delta = 24, 6, 2, 6, 12
        cnt = rng.integers(0, s + 2, size=n).astype(np.int32)
        base = np.sort(rng.integers(0, 120, size=n)).astype(np.int32)
        ts = np.zeros((n, s), np.int32)
        for i in range(n):
            c = min(int(cnt[i]), s)
            if c:
                ts[i, :c] = np.sort(rng.integers(0, 25, size=c)) + base[i]
        blocks = np.arange(10, 10 + n, dtype=np.int32)
        want = mine_reference_sequential(blocks, ts, cnt, r_, s_max, delta)
        src, dst, valid, _ = associations_dense(
            jnp.array(blocks), jnp.array(ts.T), jnp.array(cnt), r_, s_max,
            delta, window=n - 1, max_pairs=256)
        got = [(int(a), int(b)) for a, b, v in zip(src, dst, valid) if v]
        assert got == want


class TestRecordingSemantics:
    def test_association_discovered_and_directed(self):
        cfg = small_cfg()
        seq = []
        for rep in range(4):
            seq += [5, 6, 1000 + rep]
        st = run_trace(cfg, seq)
        st = mine(cfg, st)
        assert int(lookup(cfg, st, jnp.int32(5))[0]) == 6
        assert int(lookup(cfg, st, jnp.int32(6))[0]) == EMPTY

    def test_symmetric_extension(self):
        cfg = small_cfg(symmetric=True)
        seq = []
        for rep in range(4):
            seq += [5, 6, 1000 + rep]
        st = mine(cfg, run_trace(cfg, seq))
        assert int(lookup(cfg, st, jnp.int32(6))[0]) == 5

    def test_frequent_block_excluded(self):
        """A block seen more than S times in an interval is 'frequent'."""
        cfg = small_cfg(min_support=2, max_support=3)
        seq = []
        for rep in range(6):           # block 7 recorded 6 > S=3 times
            seq += [7, 8] if rep < 3 else [7, 9]
        st = run_trace(cfg, seq)
        row = None
        for i in range(int(st.mine_fill)):
            if int(st.mine_block[i]) == 7:
                row = i
        assert row is not None
        assert int(st.mine_cnt[row]) == cfg.max_support + 1  # marked frequent
        st = mine(cfg, st)
        assert int(lookup(cfg, st, jnp.int32(7))[0]) == EMPTY

    def test_mining_triggers_when_table_full(self):
        cfg = small_cfg(mine_rows=4, min_support=2)
        seq = []
        for blk in (11, 12, 13, 14):
            seq += [blk, blk]          # each becomes mining-ready
        st = run_trace(cfg, seq)
        assert int(st.n_mines) == 1
        assert int(st.mine_fill) == 0  # cleared after mining

    def test_prefetch_list_fifo(self):
        """More than P associations for one source replace FIFO (Sec 4.2.2)."""
        cfg = small_cfg(prefetch_list=2, lookahead=50, mine_rows=16)
        st = init(cfg)
        from repro.core.mithril import add_association
        for dst in (101, 102, 103):
            st = add_association(cfg, st, jnp.int32(5), jnp.int32(dst),
                                 jnp.array(True))
        vals = set(int(v) for v in lookup(cfg, st, jnp.int32(5)))
        assert vals == {103, 102}      # 101 replaced FIFO

    def test_existing_source_update_refreshes_age(self):
        """Updating a live prefetch source must touch pf_age: otherwise
        the hottest sources keep their insertion timestamp and are the
        FIRST picked by choose_victim (LRU-stale bugfix)."""
        from repro.core.hashindex import probe
        from repro.core.mithril import add_association
        cfg = small_cfg(prefetch_list=4)
        st = init(cfg)._replace(ts=jnp.int32(10))
        st = add_association(cfg, st, jnp.int32(5), jnp.int32(101),
                             jnp.array(True))
        b, way, found = probe(st.pf_key, jnp.int32(5), cfg.pf_buckets)
        assert bool(found) and int(st.pf_age[b, way]) == 10
        # new-destination update refreshes the age
        st = st._replace(ts=jnp.int32(20))
        st = add_association(cfg, st, jnp.int32(5), jnp.int32(102),
                             jnp.array(True))
        assert int(st.pf_age[b, way]) == 20
        # duplicate-destination update is still a touch
        st = st._replace(ts=jnp.int32(30))
        st = add_association(cfg, st, jnp.int32(5), jnp.int32(101),
                             jnp.array(True))
        assert int(st.pf_age[b, way]) == 30

    def test_min_support_one(self):
        cfg = small_cfg(min_support=1, mine_rows=16)
        st = run_trace(cfg, [3, 4, 3, 4])
        assert int(st.mine_fill) >= 2

    def test_ts_increments_per_record(self):
        cfg = small_cfg()
        st = run_trace(cfg, [1, 2, 3])
        assert int(st.ts) == 3


class TestBoundedMetadata:
    def test_state_shapes_fixed(self):
        cfg = small_cfg()
        st0 = init(cfg)
        st = run_trace(cfg, list(range(1000)))   # way over capacity
        for a, b in zip(jax.tree.leaves(st0), jax.tree.leaves(st)):
            assert a.shape == b.shape and a.dtype == b.dtype

    def test_metadata_budget_sizing(self):
        cfg = MithrilConfig.from_metadata_budget(2 << 20)
        assert cfg.metadata_bytes() <= (2 << 20) * 1.25
