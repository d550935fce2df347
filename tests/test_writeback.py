"""The write-back cache against the benchmark driver's plain reference.

A write-back configuration (``SimConfig(write_back=True)``) marks a
block dirty on every write, hit or miss, and writes each dirty block
back once: when a demand or a MITHRIL prefetch insertion pushes it out,
or at the end of its volume. These tests run seeded small volumes of
reads and writes of 1 to 16 blocks through a small cache, so that
evictions are constant, and hold every entry point that takes write
flags (``simulate``, ``SimSession``, ``sweep_streaming`` with recycled
lanes and with arrival gaps, an MSR-format file through the ingest) to
the 18 counts of ``bench/drivers/rw_sweep.py``'s reference.
"""

import collections
import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "bench" / "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.drivers import rw_sweep  # noqa: E402
from bench.drivers.block_sweep import sim_config  # noqa: E402
from repro.cache import SimSession, base, simulate  # noqa: E402
from repro.cache.simulator import build_step  # noqa: E402
from repro.cache.sweep import sweep, sweep_streaming  # noqa: E402
from repro.traces import ingest_msr_csv  # noqa: E402
from tiny import TINY_CONFIG  # noqa: E402

MSR = ROOT / "tests" / "fixtures" / "msr_tiny.csv"
# a 32-block cache in 16-way sets with tiny MITHRIL tables: evictions on
# most misses, mining within a few hundred requests, prefetch inserts
# that push dirty blocks out
CONFIG = {**TINY_CONFIG, "use_amp": False, "write_back": True,
          "capacity": 32, "ways": 16, "lookahead": 50, "pf_buckets": 64}
TRAFFIC = {"extent_blocks": 16, "volumes": [
    {"kind": "groups", "family": "midfreq", "length_frac": 1.0,
     "write_share": 0.6, "sizes": [[1, 0.8], [2, 0.1], [16, 0.1]],
     "params": {"n_groups": 20, "group_size": 4, "reuse": 8, "spread": 3}},
    {"kind": "hot", "family": "zipf", "length_frac": 0.8,
     "write_share": 0.9, "sizes": [[1, 0.7], [4, 0.3]],
     "params": {"alpha": 1.2, "catalog": 256}},
    {"kind": "log", "family": "seq", "length_frac": 0.6,
     "write_share": 0.8, "sizes": [[8, 0.5], [16, 0.5]],
     "params": {"n_streams": 4, "run_len": 16, "skip_prob": 0.05}},
]}
N_VOLUMES, NOMINAL = 6, 512
LANES, CHUNK = 4, 64


@pytest.fixture(scope="module")
def cfg():
    return sim_config(CONFIG)


@pytest.fixture(scope="module")
def volumes():
    entries = TRAFFIC["volumes"]
    return [rw_sweep.make_volume(TRAFFIC, entries[s % len(entries)],
                                 2**31 + 17, 0, s, NOMINAL)
            for s in range(N_VOLUMES)]


@pytest.fixture(scope="module")
def want(volumes):
    return np.array([rw_sweep.simulate(CONFIG, v["blocks"], v["writes"])
                     for v in volumes])


def _row(stats) -> list:
    """One volume's 18 counts from a ``Stats`` of unbatched leaves."""
    return rw_sweep.counts_of(
        jax.tree.map(lambda leaf: np.asarray(leaf)[None], stats))[0].tolist()


def test_volumes_mix_reads_writes_and_sizes(volumes, want):
    for v in volumes:
        assert v["blocks"].dtype == np.int32 and v["writes"].dtype == bool
        assert 0.2 < v["writes"].mean() < 0.98
    runs = np.diff(np.concatenate([v["blocks"] for v in volumes])) == 1
    assert runs.mean() > 0.3                     # multi-block requests
    # every count the cell compares is exercised: MITHRIL's prefetches
    # issued and used, writes, write-backs and flushes in every volume,
    # write hits
    assert (want[:, 3] > 0).sum() >= 4 and (want[:, 7] > 0).sum() >= 4
    assert (want[:, [14, 16, 17]] > 0).all() and (want[:, 15] > 0).any()


def test_simulate_matches_reference(cfg, volumes, want):
    for v, row in zip(volumes[:2], want):
        got = simulate(cfg, v["blocks"], writes=v["writes"])
        assert _row(got.stats) == row.tolist()


def test_session_matches_reference_however_fed(cfg, volumes, want):
    v = volumes[0]
    sess = SimSession(cfg, chunk=CHUNK)
    for lo, hi in ((0, 7), (7, 300), (300, len(v["blocks"]))):
        sess.feed(v["blocks"][lo:hi], writes=v["writes"][lo:hi])
    assert _row(sess.finish().stats) == want[0].tolist()


@pytest.mark.parametrize("async_producer", [True, False],
                         ids=["async", "sync"])
def test_streaming_recycled_lanes_match_reference(cfg, volumes, want,
                                                  async_producer):
    out = sweep_streaming(cfg, [v["blocks"] for v in volumes],
                          writes=[v["writes"] for v in volumes],
                          lane_width=LANES, chunk=CHUNK,
                          async_producer=async_producer)
    assert len(volumes) > LANES                  # lanes were recycled
    np.testing.assert_array_equal(rw_sweep.counts_of(out.result.stats), want)
    pipe = out.streaming_stats()["pipeline"]
    # blocks (4 B), valid and write flags (1 B each) for every slab slot
    assert pipe["staged_bytes"] == out.n_slabs * CHUNK * LANES * 6


def test_streaming_with_arrival_gaps_matches_reference(cfg, volumes, want):
    """Arrivals with gaps and bursts: some slabs place a volume's
    requests around invalid rows (the producer's gapped path), others in
    one run (its contiguous path)."""
    rng = np.random.default_rng(5)
    arrivals = [np.cumsum(rng.integers(0, 3, size=len(v["blocks"])))
                * (1 + i % 2) + 40 * i for i, v in enumerate(volumes)]
    out = sweep_streaming(cfg, [v["blocks"] for v in volumes],
                          writes=[v["writes"] for v in volumes],
                          arrivals=arrivals, lane_width=LANES, chunk=CHUNK)
    np.testing.assert_array_equal(rw_sweep.counts_of(out.result.stats), want)


def test_each_dirtying_is_written_back_once(cfg, volumes):
    """``writebacks + flushed`` is the number of clean-to-dirty
    transitions, counted here by a plain LRU of dict order (MITHRIL off,
    so every eviction is the least recently used block); the flush is
    the dirty table's population at the end of the volume."""
    plain = dataclasses.replace(cfg, use_mithril=False,
                                ways=CONFIG["capacity"])
    init_carry, step = build_step(plain)
    for v in volumes[:2]:
        xs = (jnp.asarray(v["blocks"]), jnp.asarray(v["writes"]))
        carry, _ = jax.jit(lambda x: lax.scan(step, init_carry(), x))(xs)
        lru: collections.OrderedDict = collections.OrderedDict()
        transitions = 0
        for block, write in zip(v["blocks"].tolist(), v["writes"].tolist()):
            if block in lru:
                lru.move_to_end(block)
            elif len(lru) == CONFIG["capacity"]:
                lru.popitem(last=False)
            dirty = lru.get(block, False)
            transitions += write and not dirty
            lru[block] = dirty or write
        st = carry["stats"]
        assert int(st.writebacks) + int(st.dirty_resident) == transitions
        assert int(st.dirty_resident) == int(carry["cache"].dirty.sum()) \
            == sum(lru.values())


def test_prefetch_insert_writes_back_its_dirty_victim():
    """One 2-way set: a written block, then a read one; a prefetch
    insert evicts the written (least recent) block and reports it dirty;
    the prefetched block enters clean."""
    st = base.init_cache(2, ways=2, write_back=True)
    st, _, _, _, dirtied = base.access(st, jnp.int32(5), write=True)
    assert bool(dirtied)
    st, _, _, _, dirtied = base.access(st, jnp.int32(6), write=False)
    assert not bool(dirtied)
    st, issued, ev = base.insert_prefetch(st, jnp.int32(7),
                                          jnp.int32(base.PF_MITHRIL),
                                          jnp.array(True))
    assert bool(issued) and int(ev.block) == 5 and bool(ev.dirty)
    assert int(st.dirty.sum()) == 0
    # a write hit on the unused prefetched block consumes it, dirty
    st, hit, used, _, dirtied = base.access(st, jnp.int32(7), write=True)
    assert bool(hit) and int(used) == base.PF_MITHRIL and bool(dirtied)
    st, _, _, _, dirtied = base.access(st, jnp.int32(7), write=True)
    assert not bool(dirtied)                     # already dirty


def test_prefetch_victims_are_written_back_in_the_sweep(cfg, volumes, want):
    """On the test volumes MITHRIL's prefetch inserts push dirty blocks
    out: the control that drops them unwritten reads fewer write-backs,
    and the program counts them as the reference does."""
    dropped = np.array([rw_sweep.simulate(CONFIG, v["blocks"], v["writes"],
                                          "prefetch_evicts_clean")
                        for v in volumes])
    assert (dropped[:, 16] < want[:, 16]).any()
    assert (dropped[:, :16] == want[:, :16]).all()


def test_msr_file_through_ingest_and_streaming(cfg):
    blocks, writes = ingest_msr_csv(str(MSR), writes=True)
    assert writes.any() and not writes.all()
    vols = [blocks.astype(np.int32), (blocks[::-1] + 7).astype(np.int32)]
    flags = [writes, writes[::-1]]
    out = sweep_streaming(cfg, vols, writes=flags, lane_width=LANES,
                          chunk=CHUNK)
    np.testing.assert_array_equal(
        rw_sweep.counts_of(out.result.stats),
        [rw_sweep.simulate(CONFIG, b, w) for b, w in zip(vols, flags)])


def test_write_flags_need_a_write_back_configuration(cfg):
    read_only = dataclasses.replace(cfg, write_back=False)
    blocks = np.arange(8, dtype=np.int32)
    with pytest.raises(ValueError, match="write-back"):
        simulate(read_only, blocks, writes=np.ones(8, bool))
    with pytest.raises(ValueError, match="write-back"):
        sweep_streaming(read_only, [blocks], writes=[np.ones(8, bool)])
    with pytest.raises(ValueError, match="write-back"):
        base.access(base.init_cache(4, ways=4), jnp.int32(1), write=True)
    assert base.access(base.init_cache(4, ways=4), jnp.int32(1))[4] is None
    with pytest.raises(ValueError, match="sweep_streaming"):
        sweep(cfg, blocks[None])
