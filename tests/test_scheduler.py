"""Corpus-scale sweeps: lane schedule invariance, input checks, sharding.

``sweep_streaming`` runs a heterogeneous trace corpus through one pool
of recycled lanes, so the whole corpus costs ONE compiled executable
per config (DESIGN.md §8), its per-trace results are bit-identical to
the serial ``simulate`` whatever the lane width and chunk, and sharding
the lane axis over devices changes nothing but wall-clock — per-lane
results stay bit-identical to the single-device path (pinned here on a
forced 4-device CPU subprocess).
"""

import ast
import dataclasses
import inspect
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.cache import SimConfig, pad_traces, simulate, sweep, \
    sweep_streaming
from repro.cache.sweep import reset_runners
from repro.core import MithrilConfig
from repro.traces import mixed

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

CFG = SimConfig(capacity=128, use_mithril=True, use_amp=True,
                mithril=MithrilConfig(min_support=2, max_support=6,
                                      lookahead=30, rec_buckets=256,
                                      rec_ways=4, mine_rows=32,
                                      pf_buckets=256, pf_ways=4))


@pytest.fixture(scope="module")
def corpus():
    # heterogeneous lengths spanning several chunk multiples so lanes
    # drain at different slabs and recycle
    return {f"t{i:02d}": mixed(220 + 173 * i, w_seq=0.3, w_assoc=0.4,
                               w_zipf=0.3, seed=40 + i) for i in range(7)}


def _assert_same(a, b, what):
    for field, x, y in zip(a.stats._fields, a.stats, b.stats):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"stats.{field} depends on {what}")
    np.testing.assert_array_equal(a.hit_curve, b.hit_curve,
                                  err_msg=f"hit curve depends on {what}")


class TestLaneSchedule:
    def test_variable_width_plans_stay_bit_identical(self, corpus):
        """The schedule is invisible in the results: two lane widths
        and two chunks, so traces drain and recycle at different slabs,
        give identical stats in the original trace order."""
        a = sweep_streaming(CFG, corpus, lane_width=2, chunk=256)
        b = sweep_streaming(CFG, corpus, lane_width=5, chunk=100)
        assert (a.n_slabs, a.chunk) != (b.n_slabs, b.chunk)
        _assert_same(a.result, b.result, "lane width and chunk")


class TestCorpusSweep:
    def test_bit_identical_to_simulate_one_compile(self, corpus):
        reset_runners()
        res = sweep_streaming(CFG, corpus, lane_width=3, chunk=256).result
        # one (chunk, lane_width) slab shape serves the whole corpus
        assert res.compiles == 1, res.compiles
        for i, (name, trace) in enumerate(corpus.items()):
            ref = simulate(CFG, trace)
            got = res.result(i)
            for field, a, b in zip(ref.stats._fields, got.stats, ref.stats):
                np.testing.assert_array_equal(
                    np.asarray(a), np.asarray(b),
                    err_msg=f"stats.{field} diverged on {name}")
            np.testing.assert_array_equal(
                got.hit_curve, np.asarray(ref.hit_curve),
                err_msg=f"hit curve diverged on {name}")

    def test_matches_unscheduled_sweep_any_lane_width(self, corpus):
        """Recycled lanes match ``sweep``, which gives every trace its
        own lane: at any pool width the stats come back the same, in
        the original trace order."""
        suite = pad_traces(corpus)
        ref = sweep(CFG, suite.blocks, suite.lengths, chunk=256)
        for width in (3, 7):
            got = sweep_streaming(CFG, corpus, lane_width=width, chunk=256)
            _assert_same(ref, got.result, f"lane width {width}")

    def test_accepts_padded_batch_input(self, corpus):
        suite = pad_traces(corpus)
        a = sweep_streaming(CFG, corpus, lane_width=3, chunk=256).result
        for traces, lengths in ((suite, None),
                                (suite.blocks, suite.lengths)):
            b = sweep_streaming(CFG, traces, lengths=lengths, lane_width=3,
                                chunk=256).result
            np.testing.assert_array_equal(a.hit_curve, b.hit_curve)
            np.testing.assert_array_equal(np.asarray(a.stats.hits),
                                          np.asarray(b.stats.hits))

    @pytest.mark.parametrize("case", [
        "negative_length", "length_past_axis", "lengths_shape",
        "one_d_blocks", "lengths_beside_suite", "writes_count"])
    def test_rejects_bad_input(self, corpus, case):
        """A malformed sweep input raises, never runs as something else:
        a negative length would become an all-masked zero-stat lane, a
        length past the trace axis would read padding, and ``lengths``
        beside a suite (which carries its own) would silently win or
        lose. ``sweep`` hands its input to the same check."""
        blocks = np.zeros((3, 100), np.int32)
        cfg, kw = CFG, {}
        if case == "negative_length":
            traces, kw["lengths"], match = blocks, np.array([50, -1, 100]), \
                "lengths"
        elif case == "length_past_axis":
            traces, kw["lengths"], match = blocks, np.array([50, 101, 9]), \
                "lengths"
        elif case == "lengths_shape":
            traces, kw["lengths"], match = blocks, np.array([50, 100]), \
                "lengths"
        elif case == "one_d_blocks":
            traces, match = blocks[0], r"\(B, T\)"
        elif case == "lengths_beside_suite":
            traces, kw["lengths"], match = corpus, \
                np.ones(len(corpus), np.int64), "lengths"
        else:
            cfg = dataclasses.replace(CFG, write_back=True)
            traces, kw["writes"], match = blocks, [np.ones(100, bool)], \
                "writes"
        with pytest.raises(ValueError, match=match):
            sweep_streaming(cfg, traces, **kw)
        if isinstance(traces, np.ndarray) and "writes" not in kw:
            with pytest.raises(ValueError, match=match):
                sweep(cfg, traces, **kw)


def test_sharded_sweep_bit_identical_to_single_device():
    """Lane-axis device sharding must be invisible in the results.

    jax's device count is fixed at backend init, so the 4-device CPU
    check runs in a subprocess with --xla_force_host_platform_device_count.
    """
    script = textwrap.dedent("""
        import numpy as np
        from repro.cache import SimConfig, sweep_streaming
        from repro.core import MithrilConfig
        from repro.traces import mixed
        import jax
        assert jax.local_device_count() == 4, jax.local_device_count()
        traces = {f"t{i}": mixed(250 + 111 * i, 0.3, 0.4, 0.3, seed=60 + i)
                  for i in range(8)}
        cfg = SimConfig(capacity=64, use_mithril=True, use_amp=True,
                        mithril=MithrilConfig(
                            min_support=2, max_support=4, lookahead=20,
                            rec_buckets=128, rec_ways=2, mine_rows=16,
                            pf_buckets=128, pf_ways=2))
        single = sweep_streaming(cfg, traces, lane_width=4, chunk=128,
                                 shard=False)
        sharded = sweep_streaming(cfg, traces, lane_width=4, chunk=128,
                                  shard=True)
        assert (single.n_shards, sharded.n_shards) == (1, 4)
        single, sharded = single.result, sharded.result
        for f, a, b in zip(single.stats._fields, single.stats,
                           sharded.stats):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=f)
        np.testing.assert_array_equal(single.hit_curve, sharded.hit_curve)
        assert sharded.compiles == 1, sharded.compiles
        print("SHARDED-OK", sharded.compiles)
    """)
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=540)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    assert "SHARDED-OK" in out.stdout


def _calls_cond_or_switch(src: str) -> bool:
    """True when the code CALLS lax.cond / lax.switch (AST-level, so
    docstrings and comments that merely mention them don't count)."""
    tree = ast.parse(textwrap.dedent(src))
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if isinstance(f, ast.Attribute) and f.attr in ("cond", "switch"):
            base = f.value
            if (isinstance(base, ast.Name) and base.id == "lax") or \
                    (isinstance(base, ast.Attribute) and base.attr == "lax"):
                return True
    return False


def test_no_cond_in_request_path_sources():
    """ISSUE 4 acceptance: no lax.cond / lax.switch anywhere in the
    vmapped request step — the record path (PR 3) and now AMP are all
    scatter form. The mining BARRIERS (core.mithril maybe_mine /
    mine_batched) legitimately keep theirs: they run outside vmap."""
    import repro.cache.amp
    import repro.cache.base
    import repro.cache.pg
    import repro.learn.policy
    from repro.cache.simulator import build_segments
    from repro.core.mithril import add_association, assoc_count, record_event
    sources = {
        "cache/amp.py": inspect.getsource(repro.cache.amp),
        "cache/base.py": inspect.getsource(repro.cache.base),
        "cache/pg.py": inspect.getsource(repro.cache.pg),
        "learn/policy.py": inspect.getsource(repro.learn.policy),
        "simulator.build_segments": inspect.getsource(build_segments),
        "mithril.record_event": inspect.getsource(record_event),
        "mithril.add_association": inspect.getsource(add_association),
        "mithril.assoc_count": inspect.getsource(assoc_count),
    }
    for name, src in sources.items():
        assert not _calls_cond_or_switch(src), \
            f"{name} reintroduced a per-request cond/switch"
