"""Async producer pipeline: bit-identity, stalls, and the serve split.

ISSUE 9: ``sweep_streaming`` runs its host scheduler on a background
thread feeding a thread-safe ``RingBuffer``, with a drain thread
materializing hit slabs off-device as they complete. Admission and
placement depend only on host-known cursors, so the threaded pipeline
must be bit-identical to the synchronous fallback
(``async_producer=False``) under ANY ring depth, chunk size, arrival
process or admission order — these tests pin that, plus the ring's
stall accounting, the argument validation at the ``sweep_streaming``
boundary, the forced-multi-device sharded staging path
(``dist.sharding.ring_put``), and ``TieredServeEngine``'s pipelined
step keeping its deterministic counters while splitting wall-clock
into host vs device time.
"""

import copy
import os
import subprocess
import sys
import textwrap
import threading
import time

import numpy as np
import pytest

from benchmarks.compare import compare
from repro.cache import SimConfig
from repro.cache.sweep import RingBuffer, sweep_streaming
from repro.cache.tiered import TieredKVCache
from repro.core import MithrilConfig
from repro.launch.serve import TieredServeEngine
from repro.traces import arrival_process, mixed

HERE = os.path.dirname(__file__)
SRC = os.path.abspath(os.path.join(HERE, "..", "src"))

CFG = SimConfig(capacity=128, use_mithril=True, use_amp=True,
                mithril=MithrilConfig(min_support=2, max_support=6,
                                      lookahead=30, rec_buckets=256,
                                      rec_ways=4, mine_rows=32,
                                      pf_buckets=256, pf_ways=4))


def _corpus(seed: int, n: int = 6):
    rng = np.random.default_rng(seed)
    return {f"t{i:02d}": mixed(int(rng.integers(150, 420)),
                               0.3, 0.4, 0.3, seed=seed * 31 + i)
            for i in range(n)}


def _assert_bit_identical(a, b):
    np.testing.assert_array_equal(a.result.hit_curve, b.result.hit_curve)
    for f in a.result.stats._fields:
        np.testing.assert_array_equal(
            getattr(a.result.stats, f), getattr(b.result.stats, f), err_msg=f)


class TestRingBuffer:
    def test_empty_pop_raises_clear_error(self):
        ring = RingBuffer(depth=2)
        with pytest.raises(RuntimeError, match="empty"):
            ring.pop()

    def test_nonblocking_semantics_unchanged(self):
        ring = RingBuffer(depth=2)
        ring.push("a")
        ring.push("b")
        with pytest.raises(RuntimeError, match="full"):
            ring.push("c")
        assert ring.pop() == "a" and ring.pop() == "b"

    def test_push_on_closed_ring_raises(self):
        ring = RingBuffer(depth=2)
        ring.close()
        with pytest.raises(RuntimeError, match="closed"):
            ring.push("a")

    def test_blocking_pop_returns_none_on_closed_drained_ring(self):
        ring = RingBuffer(depth=2)
        ring.push("a")
        ring.close()
        assert ring.pop(block=True) == "a"
        assert ring.pop(block=True) is None

    def test_producer_stall_accounting_with_slow_consumer(self):
        # a deliberately slow consumer: the producer thread fills the
        # depth-1 ring and must block on every subsequent push
        ring = RingBuffer(depth=1)
        n_items = 5

        def producer():
            for i in range(n_items):
                ring.push(i, block=True)
            ring.close()

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        got = []
        while True:
            time.sleep(0.02)            # consumer is the bottleneck
            item = ring.pop(block=True)
            if item is None:
                break
            got.append(item)
        t.join()
        assert got == list(range(n_items))      # FIFO preserved
        assert ring.push_stalls >= 1            # producer waited on full
        assert ring.pop_stalls == 0

    def test_consumer_stall_accounting_with_slow_producer(self):
        ring = RingBuffer(depth=4)

        def producer():
            time.sleep(0.05)            # producer is the bottleneck
            ring.push("x", block=True)
            ring.close()

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        assert ring.pop(block=True) == "x"
        assert ring.pop(block=True) is None
        t.join()
        assert ring.pop_stalls >= 1             # consumer waited on empty


class TestBoundaryValidation:
    @pytest.mark.parametrize("depth", [0, -1, 2.5, "4", None, True])
    def test_bad_ring_depth_rejected(self, depth):
        with pytest.raises(ValueError, match="ring.?depth"):
            sweep_streaming(CFG, _corpus(1, n=2), ring_depth=depth)

    @pytest.mark.parametrize("flag", ["yes", 1, None])
    def test_bad_async_producer_rejected(self, flag):
        with pytest.raises(ValueError, match="async_producer"):
            sweep_streaming(CFG, _corpus(1, n=2), async_producer=flag)

    @pytest.mark.parametrize("depth", [0, -3])
    def test_ring_buffer_depth_validated(self, depth):
        with pytest.raises(ValueError, match="depth"):
            RingBuffer(depth=depth)


class TestAsyncBitIdentity:
    def test_stress_random_depths_chunks_arrivals_orders(self):
        # random ring depths, chunk sizes, arrival gaps and admission
        # orders; chunk/width pairs are drawn from a small set so the
        # shapes share compiled runners across rounds
        shapes = [(3, 48), (2, 96)]
        for round_ in range(4):
            rng = np.random.default_rng(100 + round_)
            corpus = _corpus(seed=round_, n=int(rng.integers(4, 8)))
            # admission order is the dict order: shuffle it
            names = list(corpus)
            rng.shuffle(names)
            corpus = {k: corpus[k] for k in names}
            if round_ % 2:
                arr = arrival_process(
                    corpus, mode="onoff",
                    burst_len=int(rng.integers(8, 64)),
                    idle_len=int(rng.integers(4, 40)),
                    stagger=int(rng.integers(0, 80)), seed=round_)
                arrivals = [arr[k] for k in corpus]
            else:
                arrivals = None
            w, chunk = shapes[round_ % len(shapes)]
            depth = int(rng.integers(1, 6))
            kw = dict(lane_width=w, chunk=chunk, arrivals=arrivals)
            a = sweep_streaming(CFG, corpus, ring_depth=depth,
                                async_producer=True, **kw)
            s = sweep_streaming(CFG, corpus, ring_depth=depth,
                                async_producer=False, **kw)
            _assert_bit_identical(a, s)
            # deterministic schedule counters match too
            sa, ss = a.streaming_stats(), s.streaming_stats()
            for k in ("lane_width", "chunk", "n_slabs", "lane_steps",
                      "ideal_lane_steps", "waste_ratio"):
                assert sa[k] == ss[k], k

    def test_pipeline_telemetry_shape(self):
        stream = sweep_streaming(CFG, _corpus(7, n=3), lane_width=3,
                                 chunk=48, async_producer=True)
        p = stream.streaming_stats()["pipeline"]
        for k in ("produce_s", "staging_wait_s", "consume_s", "drain_s",
                  "wall_s", "producer_stalls", "consumer_stalls",
                  "overlap"):
            assert k in p
        assert p["wall_s"] >= 0 and 0.0 <= p["overlap"] <= 1.0
        assert p["producer_stalls"] >= 0 and p["consumer_stalls"] >= 0
        assert stream.streaming_stats()["async_producer"] is True

    def test_produce_time_leaves_out_the_staging_wait(self, monkeypatch):
        """A slow drain holds the staging buffers, so the producer waits
        for a free one: that wait is ``staging_wait_s``, and
        ``produce_s`` stays the producer's own work, well under the
        wall clock."""
        import contextlib
        import importlib
        sweep_mod = importlib.import_module("repro.cache.sweep")
        real = sweep_mod._span

        @contextlib.contextmanager
        def slow_drain(name, *args, **kw):
            with real(name, *args, **kw):
                if name == "sweep.drain":
                    time.sleep(0.05)
                yield

        monkeypatch.setattr(sweep_mod, "_span", slow_drain)
        stream = sweep_streaming(CFG, _corpus(3, n=3), lane_width=3,
                                 chunk=32, ring_depth=1,
                                 async_producer=True)
        p = stream.streaming_stats()["pipeline"]
        assert stream.n_slabs >= 8
        assert p["drain_s"] >= 0.05 * stream.n_slabs
        assert p["produce_s"] < 0.25 * p["wall_s"]
        assert p["staging_wait_s"] > p["produce_s"]

    def test_zero_length_tenants_drain_in_async_mode(self):
        corpus = {"empty_a": np.empty((0,), np.int32),
                  "real": mixed(120, 0.3, 0.4, 0.3, seed=5),
                  "empty_b": np.empty((0,), np.int32)}
        a = sweep_streaming(CFG, corpus, lane_width=2, chunk=48,
                            async_producer=True)
        s = sweep_streaming(CFG, corpus, lane_width=2, chunk=48,
                            async_producer=False)
        _assert_bit_identical(a, s)
        assert a.result.hit_ratios().shape == (3,)

    def test_producer_exception_propagates(self):
        bad = {"t0": mixed(100, 0.3, 0.4, 0.3, seed=1)}
        # arrivals validated at the boundary are fine; force a producer
        # error by handing a non-integer block array the runner rejects
        with pytest.raises(Exception):
            sweep_streaming(CFG, [np.array(["x", "y"], object)],
                            async_producer=True)
        # the engine stays usable after a failed run
        out = sweep_streaming(CFG, bad, lane_width=1, chunk=48)
        assert out.result.hit_ratios().shape == (1,)


@pytest.mark.slow
def test_async_sharded_staging_bit_identical_forced_4dev():
    """ring_put-staged async slabs == sync replicated slabs on 4 devices."""
    script = textwrap.dedent("""
        import jax, numpy as np
        assert jax.local_device_count() == 4, jax.local_device_count()
        from repro.cache import SimConfig
        from repro.cache.sweep import sweep_streaming
        from repro.core import MithrilConfig
        from repro.traces import arrival_process, mixed

        cfg = SimConfig(capacity=64, use_mithril=True,
                        mithril=MithrilConfig(min_support=2, max_support=4,
                                              lookahead=20, rec_buckets=64,
                                              rec_ways=2, mine_rows=16,
                                              pf_buckets=64, pf_ways=2))
        corpus = {f"t{i}": mixed(180 - 11 * i, 0.3, 0.4, 0.3, seed=50 + i)
                  for i in range(6)}
        arr = arrival_process(corpus, mode="onoff", burst_len=24,
                              idle_len=9, stagger=20, seed=2)
        kw = dict(arrivals=[arr[k] for k in corpus], lane_width=4,
                  chunk=32, shard=True)
        a = sweep_streaming(cfg, corpus, async_producer=True, **kw)
        s = sweep_streaming(cfg, corpus, async_producer=False, **kw)
        assert np.array_equal(a.result.hit_curve, s.result.hit_curve)
        for f in a.result.stats._fields:
            assert np.array_equal(getattr(a.result.stats, f),
                                  getattr(s.result.stats, f)), f
        single = sweep_streaming(cfg, corpus, async_producer=True,
                                 arrivals=kw["arrivals"], lane_width=4,
                                 chunk=32, shard=False)
        assert np.array_equal(a.result.hit_curve,
                              single.result.hit_curve)
        print("SHARDED_ASYNC_OK")
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "SHARDED_ASYNC_OK" in proc.stdout


class TestServeWallClockSplit:
    def _engine(self, seed=0):
        tier = TieredKVCache(n_host_pages=64, n_hbm_slots=13, page_size=8,
                             n_kv=2, head_dim=32,
                             mithril_cfg=MithrilConfig(
                                 min_support=2, max_support=8, lookahead=40,
                                 rec_buckets=128, rec_ways=4, mine_rows=8,
                                 pf_buckets=128, pf_ways=4,
                                 prefetch_list=3), seed=seed)
        eng = TieredServeEngine(tier, max_batch=3, n_q_heads=4, seed=seed)
        rng = np.random.default_rng(seed)
        sets = [rng.choice(64, 4, replace=False) for _ in range(4)]
        for rid in range(8):
            eng.submit(rid, sets[rid % 4], 2 + rid % 3,
                       arrival=(rid // 2) * 3)
        return eng

    def test_wall_splits_into_host_and_device(self):
        m = self._engine().run()
        assert m["host_seconds"] >= 0 and m["device_wait_seconds"] >= 0
        assert m["wall_seconds"] == pytest.approx(
            m["host_seconds"] + m["device_wait_seconds"], abs=1e-3)

    def test_pipelined_counters_deterministic_across_runs(self):
        det = ("requests", "tokens", "steps", "mean_batch_occupancy",
               "turnaround_steps_p50", "turnaround_steps_p95",
               "turnaround_steps_p99", "tier")
        a, b = self._engine().run(), self._engine().run()
        for k in det:
            assert a[k] == b[k], k

    def test_no_launch_left_in_flight_after_run(self):
        eng = self._engine()
        eng.run()
        assert eng._pending is None


# ---------------------------------------------------------------------------
# the "streaming" gate in benchmarks.compare (round-trip style, like
# tests/test_compare_learned.py)
# ---------------------------------------------------------------------------

def _streaming_entry(**kw):
    entry = {
        "job": "pipeline_quick", "config": "async", "lane_width": 4,
        "chunk": 256, "n_slabs": 30, "lane_steps": 30720,
        "ideal_lane_steps": 17055, "waste_ratio": 0.4448,
        "async_producer": True, "hit_ratio_mean": 0.4321,
        "pipeline": {"produce_s": 0.2, "consume_s": 1.0, "drain_s": 0.3,
                     "wall_s": 1.1, "producer_stalls": 1,
                     "consumer_stalls": 20, "overlap": 0.27},
    }
    entry.update(kw)
    return entry


def _doc(streaming):
    sweep = {"job": "j", "config": "c", "hit_ratios": [0.5],
             "seconds": 1.0, "compiles": 1}
    return {"meta": {"suite": "quick", "quick": True, "trace_len": 100,
                     "corpus_scale": "quick", "corpus_len": 50,
                     "n_devices": 1},
            "jobs": [], "sweeps": [sweep], "streaming": streaming}


class TestStreamingCompareGate:
    def test_identical_docs_pass(self):
        doc = _doc([_streaming_entry(),
                    _streaming_entry(config="sync", async_producer=False)])
        failures, warnings, _, _ = compare(doc, copy.deepcopy(doc), 0.2)
        assert not failures and not warnings

    @pytest.mark.parametrize("field,drifted", [
        ("n_slabs", 31), ("lane_steps", 30721), ("waste_ratio", 0.4449),
        ("lane_width", 8), ("chunk", 128), ("async_producer", False),
        ("hit_ratio_mean", 0.4322)])
    def test_deterministic_counter_drift_fails(self, field, drifted):
        base = _doc([_streaming_entry()])
        fresh = _doc([_streaming_entry(**{field: drifted})])
        failures, _, _, _ = compare(fresh, base, 0.2)
        assert any("streaming" in f and field in f for f in failures)

    def test_missing_pipeline_telemetry_fails(self):
        base = _doc([_streaming_entry()])
        entry = _streaming_entry()
        del entry["pipeline"]
        failures, _, _, _ = compare(_doc([entry]), base, 0.2)
        assert any("pipeline telemetry missing" in f for f in failures)

    def test_wallclock_and_overlap_only_warn(self):
        base = _doc([_streaming_entry()])
        fresh = _doc([_streaming_entry(
            pipeline={"produce_s": 0.2, "consume_s": 3.0, "drain_s": 0.3,
                      "wall_s": 3.3, "producer_stalls": 9,
                      "consumer_stalls": 0, "overlap": 0.0})])
        failures, warnings, _, _ = compare(fresh, base, 0.2)
        assert not failures
        assert any("wall-clock" in w for w in warnings)
        assert any("overlap" in w for w in warnings)

    def test_missing_fresh_entry_fails(self):
        base = _doc([_streaming_entry()])
        failures, _, _, _ = compare(_doc([]), base, 0.2)
        assert any("missing from fresh run" in f for f in failures)

    def test_baseline_without_section_warns_and_skips(self):
        fresh = _doc([_streaming_entry()])
        base = _doc([])
        del base["streaming"]
        failures, warnings, _, _ = compare(fresh, base, 0.2)
        assert not failures
        assert any("streaming" in w and "older schema" in w
                   for w in warnings)

    def test_new_fresh_entry_is_noted(self):
        base = _doc([_streaming_entry()])
        fresh = _doc([_streaming_entry(),
                      _streaming_entry(config="sync")])
        failures, _, notes, _ = compare(fresh, base, 0.2)
        assert not failures
        assert any("not in baseline" in n for n in notes)
