"""The streaming sweep's instrumentation, on the CPU at tiny sizes.

The chunk runner counts each lane's mining passes by path (one lane
alone, or an all-lanes pass), and the engine times its stages under
``sweep.*`` profiler spans. Neither may change a result: these tests pin
the counters against the plain reference's own mining passes, across
the async and sync producers and across devices, and the results with
the profiler on against those with it off.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "bench" / "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench.lib import generate, reference  # noqa: E402
from bench.run import sim_config  # noqa: E402
from repro.cache.sweep import MINE_PASS_KEYS, sweep_streaming  # noqa: E402
from tiny import TINY_CONFIG, TRAFFIC  # noqa: E402

SPANS = ("sweep.setup", "sweep.produce", "sweep.staging_wait",
         "sweep.stage", "sweep.reset", "sweep.dispatch", "sweep.drain",
         "sweep.harvest")
LANES, CHUNK = 4, 64


def _volumes():
    """Eight volumes through four lanes, so lanes recycle. The first two
    are the same volume, admitted on the same step: their mining tables
    fill together, which forces all-lanes passes beside the solo ones."""
    vols = [v.blocks for v in generate.make_job(TRAFFIC, 7, 0, 6, 384)]
    return [vols[0], *vols, vols[1]]


def _sweep(**kw):
    kw = {"lane_width": LANES, "chunk": CHUNK, **kw}
    return sweep_streaming(sim_config(TINY_CONFIG), _volumes(), **kw)


@pytest.fixture(scope="module")
def stream():
    return _sweep()


def test_mined_lanes_equal_the_serial_passes_per_volume(stream,
                                                        monkeypatch):
    passes, calls = [], []
    real = reference._Mithril.mine

    def counted(self):
        calls.append(1)
        return real(self)

    monkeypatch.setattr(reference._Mithril, "mine", counted)
    for blocks in _volumes():
        before = len(calls)
        reference.simulate(TINY_CONFIG, blocks)
        passes.append(len(calls) - before)

    mp = stream.mine_passes
    solo, fused_lanes, fused = mp.T
    np.testing.assert_array_equal(solo + fused_lanes, passes)
    assert solo.sum() > 0 and fused_lanes.sum() > 0   # alone and beside others
    assert 0 < 2 * fused.sum() <= fused_lanes.sum()   # >= 2 lanes a pass
    assert stream.streaming_stats()["mining"] == dict(
        zip(MINE_PASS_KEYS, map(int, mp.sum(axis=0))))


def test_mining_counters_same_for_async_and_sync_producers(stream):
    sync = _sweep(async_producer=False)
    np.testing.assert_array_equal(stream.mine_passes, sync.mine_passes)
    assert stream.streaming_stats()["mining"] == \
        sync.streaming_stats()["mining"]


def test_mined_lanes_same_on_one_and_four_devices():
    """Four virtual devices hold one lane each: every pass is a one-lane
    pass there, and each device counts its own, but every volume is
    mined as often as on one device."""
    script = textwrap.dedent("""
        import json, sys
        import jax
        assert jax.local_device_count() == 4
        sys.path[:0] = [%(root)r, %(tests)r]
        from test_sweep_tracing import _sweep
        out = {}
        for shard in (True, False):
            s = _sweep(shard=shard)
            out[str(s.n_shards)] = {"passes": s.mine_passes.tolist(),
                                    "mining": s.streaming_stats()["mining"]}
        print(json.dumps(out))
    """) % {"root": str(ROOT), "tests": str(ROOT / "tests")}
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(
                   [str(ROOT / "src"), os.environ.get("PYTHONPATH", "")]))
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    import json
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    one, four = (np.array(out[k]["passes"]) for k in ("1", "4"))
    np.testing.assert_array_equal(one[:, 0] + one[:, 1],
                                  four[:, 0] + four[:, 1])
    assert one[:, 1].sum() > 0
    assert out["4"]["mining"]["fused_lanes"] == 0


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The same sweep run with the profiler on, and its trace."""
    import jax

    from bench.lib import trace

    path = tmp_path_factory.mktemp("sweep_trace")
    with jax.profiler.trace(str(path)):
        out = _sweep()
    return out, trace.find_xplane(str(path))


def test_results_bit_identical_with_the_profiler_on(stream, traced):
    on, _ = traced
    np.testing.assert_array_equal(on.result.hit_curve,
                                  stream.result.hit_curve)
    for f in stream.result.stats._fields:
        np.testing.assert_array_equal(getattr(on.result.stats, f),
                                      getattr(stream.result.stats, f))
    np.testing.assert_array_equal(on.mine_passes, stream.mine_passes)


def test_host_plane_holds_every_sweep_span(traced):
    from jax.profiler import ProfileData

    from bench.lib import trace

    out, path = traced
    events = [e for p in ProfileData.from_file(path).planes
              if p.name == trace.HOST_PLANE
              for ln in p.lines for e in ln.events
              if e.name.startswith("sweep.")]
    assert {e.name for e in events} == set(SPANS)
    # a per-slab span carries its slab's index; one span a slab
    dispatch = sorted(dict(e.stats)["slab"] for e in events
                      if e.name == "sweep.dispatch")
    assert dispatch == list(range(out.n_slabs))
