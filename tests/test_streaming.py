"""Streaming ingestion engine: ring buffer, lane recycling, arrivals.

The contract (DESIGN.md §10): arrival is the primitive —
``sweep_streaming`` admits traces into a recycled lane pool as they
arrive, and the offline ``sweep`` is its special case. Pinned here:
per-trace results are bit-identical to ``sweep`` / ``simulate``
regardless of lane pool size, chunking, arrival gaps or admission
order; recycling executes no more lane-steps than a fixed-shape padded
schedule, and strictly fewer on a heterogeneous corpus, because a
drained lane idles only to the end of its slab; the incremental
``SimSession`` is slice-invariant; ``arrival_process`` is crc32-
deterministic and nondecreasing.
"""

import numpy as np
import pytest

from repro.cache import (SimConfig, SimSession, pad_traces, simulate, sweep,
                         sweep_streaming)
from repro.cache.sweep import RingBuffer
from repro.core import MithrilConfig
from repro.traces import arrival_process, mixed

CFG = SimConfig(capacity=128, use_mithril=True, use_amp=True,
                mithril=MithrilConfig(min_support=2, max_support=6,
                                      lookahead=30, rec_buckets=256,
                                      rec_ways=4, mine_rows=32,
                                      pf_buckets=256, pf_ways=4))


@pytest.fixture(scope="module")
def corpus():
    # heterogeneous lengths so recycling actually reclaims lanes: one
    # long trace pins the wall-clock while short tenants cycle through
    return {f"t{i:02d}": mixed(1400 - 190 * i if i < 5 else 160 + 40 * i,
                               w_seq=0.3, w_assoc=0.4, w_zipf=0.3,
                               seed=80 + i) for i in range(9)}


def _assert_same_results(a, b, names):
    for field, x, y in zip(a.stats._fields, a.stats, b.stats):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"stats.{field} diverged ({names})")
    np.testing.assert_array_equal(a.hit_curve, b.hit_curve,
                                  err_msg=f"hit curve diverged ({names})")


class TestStreamingBitIdentity:
    def test_replays_packed_corpus_identically(self, corpus):
        """Streaming replay of a corpus through 4 recycled lanes gives
        bit-identical hit ratios to ``sweep``, one lane per trace."""
        suite = pad_traces(corpus)
        offline = sweep(CFG, suite.blocks, suite.lengths, chunk=128)
        stream = sweep_streaming(CFG, corpus, lane_width=4, chunk=128)
        _assert_same_results(offline, stream.result, "offline vs stream")
        np.testing.assert_array_equal(offline.hit_ratios(),
                                      stream.result.hit_ratios())

    def test_lane_pool_size_is_invisible(self, corpus):
        """Recycling through 2 lanes vs 8 lanes changes scheduling only."""
        a = sweep_streaming(CFG, corpus, lane_width=2, chunk=128)
        b = sweep_streaming(CFG, corpus, lane_width=8, chunk=128)
        _assert_same_results(a.result, b.result, "W=2 vs W=8")

    def test_arrival_gaps_are_invisible(self, corpus):
        """Arrival-gated placement (gaps = masked no-op rows, staggered
        admission, mid-run recycling) must not leak into per-trace
        results: same stats as the everything-at-step-0 replay."""
        arrivals = arrival_process(corpus, mode="onoff", burst_len=48,
                                   idle_len=96, stagger=400, seed=5)
        gated = sweep_streaming(CFG, corpus, lane_width=4, chunk=128,
                                arrivals=list(arrivals.values()))
        plain = sweep_streaming(CFG, corpus, lane_width=4, chunk=128)
        _assert_same_results(gated.result, plain.result,
                             "arrival-gated vs all-at-0")

    def test_matches_serial_simulate(self, corpus):
        names = list(corpus)[:3]
        stream = sweep_streaming(CFG, {k: corpus[k] for k in names},
                                 lane_width=2, chunk=64)
        for i, name in enumerate(names):
            ref = simulate(CFG, corpus[name])
            got = stream.result.result(i)
            for field, a, b in zip(ref.stats._fields, got.stats, ref.stats):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=f"stats.{field} diverged on {name}")
            np.testing.assert_array_equal(got.hit_curve,
                                          np.asarray(ref.hit_curve))


def _fixed_lane_steps(lengths, width: int, chunk: int) -> int:
    """Lane-steps of the fixed-shape padded schedule: traces longest
    first in groups of ``width`` lanes, each group run to its first
    (longest) trace rounded up to a whole chunk (at least one)."""
    ordered = sorted((int(n) for n in lengths), reverse=True)
    return sum(-(-max(1, ordered[i]) // chunk) * chunk * width
               for i in range(0, len(ordered), width))


def _recycled_slabs(lengths, width: int, chunk: int) -> int:
    """Slabs a pool of ``width`` lanes runs when each trace, in
    submission order, takes the lane that frees first and holds it to
    the end of the slab it drains in (an empty trace takes none)."""
    free = [0] * width
    for n in lengths:
        if n:
            lane = free.index(min(free))
            free[lane] += -(-int(n) // chunk)
    return max(free)


class TestRecycling:
    def test_strictly_fewer_lane_steps_than_offline_packer(self, corpus):
        """On a heterogeneous-length corpus, lane recycling executes
        strictly fewer lane-steps than the fixed-shape padded schedule:
        short tenants cycle through reclaimed lanes instead of a lane
        group scanning its padded tail. Submitted longest first, so the
        FIFO admission order is the schedule's group order."""
        ordered = dict(sorted(corpus.items(), key=lambda kv: -len(kv[1])))
        lengths = np.array([len(t) for t in ordered.values()])
        stream = sweep_streaming(CFG, ordered, lane_width=4, chunk=128,
                                 shard=False)
        fixed = _fixed_lane_steps(lengths, 4, stream.chunk)
        assert stream.lane_steps < fixed, (stream.lane_steps, fixed)
        st = stream.streaming_stats()
        assert st["lane_steps"] == stream.lane_steps
        assert st["ideal_lane_steps"] == int(lengths.sum())
        assert 0.0 <= st["waste_ratio"] < 1.0
        assert st["waste_ratio"] < 1.0 - lengths.sum() / fixed

    @pytest.mark.parametrize("lengths", [
        [300] * 6, [900] + [60] * 7, [400, 0, 250, 0, 130], [333]],
        ids=["uniform", "skewed", "with_empty", "one_trace"])
    def test_recycled_lanes_bound_lane_steps(self, lengths):
        """Executed lane-steps stay at or under the fixed-shape padded
        schedule, and the slab count is exactly that of a pool whose
        drained lane idles only to the end of its slab before the next
        queued trace takes it."""
        rng = np.random.default_rng(len(lengths))
        traces = [rng.integers(0, 200, size=n).astype(np.int32)
                  for n in lengths]
        stream = sweep_streaming(SimConfig(capacity=32), traces,
                                 lane_width=4, chunk=64)
        assert stream.lane_steps <= _fixed_lane_steps(lengths, 4, 64)
        assert stream.n_slabs == _recycled_slabs(lengths, 4, 64)
        assert stream.streaming_stats()["ideal_lane_steps"] == sum(lengths)

    def test_zero_length_tenants_drain_at_admission(self):
        traces = {"a": mixed(300, 0.3, 0.4, 0.3, seed=1),
                  "b": np.empty((0,), np.int32),
                  "c": mixed(200, 0.3, 0.4, 0.3, seed=2)}
        stream = sweep_streaming(CFG, traces, lane_width=2, chunk=64)
        assert int(np.asarray(stream.result.stats.requests)[1]) == 0
        ref = simulate(CFG, traces["c"])
        got = stream.result.result(2)
        np.testing.assert_array_equal(np.asarray(got.stats.hits),
                                      np.asarray(ref.stats.hits))

    def test_rejects_bad_arrivals(self, corpus):
        names = list(corpus)[:2]
        sub = {k: corpus[k] for k in names}
        with pytest.raises(ValueError, match="one array per trace"):
            sweep_streaming(CFG, sub, arrivals=[np.zeros(1, np.int64)])
        bad_shape = [np.zeros(3, np.int64), None]
        with pytest.raises(ValueError, match="shape"):
            sweep_streaming(CFG, sub, arrivals=bad_shape)
        decreasing = [np.arange(len(sub[k]))[::-1] for k in names]
        with pytest.raises(ValueError, match="nondecreasing"):
            sweep_streaming(CFG, sub, arrivals=decreasing)


class TestSimSession:
    def test_slice_invariant_and_matches_simulate(self):
        trace = mixed(1000, 0.3, 0.4, 0.3, seed=3)
        ref = simulate(CFG, trace)
        rng = np.random.default_rng(0)
        sess = SimSession(CFG, chunk=128)
        i = 0
        while i < len(trace):     # feed in ragged arrival-sized pieces
            k = int(rng.integers(1, 97))
            sess.feed(trace[i: i + k])
            i += k
        got = sess.finish()
        assert sess.requests_fed == len(trace)
        for field, a, b in zip(ref.stats._fields, got.stats, ref.stats):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f"stats.{field}")
        np.testing.assert_array_equal(got.hit_curve,
                                      np.asarray(ref.hit_curve))

    def test_finish_is_terminal(self):
        sess = SimSession(CFG, chunk=32)
        sess.feed(mixed(10, 0.3, 0.4, 0.3, seed=4))
        sess.finish()
        with pytest.raises(RuntimeError, match="finished"):
            sess.feed(np.zeros(1, np.int32))
        with pytest.raises(RuntimeError, match="finished"):
            sess.finish()


class TestArrivalProcess:
    def test_deterministic_and_order_independent(self, corpus):
        a = arrival_process(corpus, mode="poisson", rate=0.5, seed=9)
        rev = dict(reversed(list(corpus.items())))
        b = arrival_process(rev, mode="poisson", rate=0.5, seed=9)
        for name in corpus:
            np.testing.assert_array_equal(a[name], b[name])

    def test_shapes_and_monotonicity(self, corpus):
        for mode in ("poisson", "onoff"):
            arr = arrival_process(corpus, mode=mode, stagger=100, seed=2)
            for name, trace in corpus.items():
                steps = arr[name]
                assert steps.shape == (len(trace),)
                assert steps.dtype == np.int64
                assert (steps >= 0).all()
                assert (np.diff(steps) >= 0).all()

    def test_onoff_has_idle_gaps(self, corpus):
        arr = arrival_process(corpus, mode="onoff", burst_len=16,
                              idle_len=64, seed=3)
        name = next(iter(corpus))
        gaps = np.diff(arr[name])
        assert (gaps == 64 + 1).any()     # idle gap between bursts
        assert (gaps == 1).any()          # back-to-back inside a burst

    def test_rejects_bad_params(self, corpus):
        with pytest.raises(ValueError, match="mode"):
            arrival_process(corpus, mode="uniform")
        with pytest.raises(ValueError, match="rate"):
            arrival_process(corpus, rate=0.0)
        with pytest.raises(ValueError, match="burst_len"):
            arrival_process(corpus, mode="onoff", burst_len=0)


def test_ring_buffer_bounds():
    ring = RingBuffer(depth=2)
    assert ring.empty and not ring.full and len(ring) == 0
    ring.push("a")
    ring.push("b")
    assert ring.full
    with pytest.raises(RuntimeError, match="full"):
        ring.push("c")
    assert ring.pop() == "a"
    ring.push("c")
    assert ring.pop() == "b" and ring.pop() == "c"
    with pytest.raises(ValueError, match="depth"):
        RingBuffer(depth=0)
