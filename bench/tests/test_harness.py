"""The harness end to end on the CPU at a tiny size: adding a cell, a
configuration, a traffic mix or a metric is adding files, and so is a
deployment with new semantics, which brings its own driver
(``test_drivers.py``); a run whose timed path is broken underneath comes
out not correct; and a run without a TPU, or outside a checkout of the
program, prints no result."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run
from tiny import write_root

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

NEW_METRIC = '''"""Hits per second over the window (a metric added as a file)."""


def read(run):
    return sum(int(j.counts[:, 1].sum()) for j in run.jobs) / run.window_s
'''


@pytest.fixture
def cpu_devices():
    import jax
    return jax.devices()


def measure(root, devices, seed=2**31 + 3):
    cell = run.load_cell(root, "t-cell")
    return run.measure(root, cell, seed, 0.0, False, devices)


def test_new_cell_and_metric_are_files(tmp_path, cpu_devices):
    root = write_root(tmp_path, BENCH / "metrics",
                      extra_metrics=[("hits_per_s", NEW_METRIC)])
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["end_to_end"].append({"name": "hits_per_s", "unit": "hit/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    out = measure(root, cpu_devices)
    assert out["correct"] and out["failed"] == 0
    assert out["attempted"] == 6
    assert set(out["metrics"]) == {"req_per_s", "setup_s", "hits_per_s"}
    assert out["metrics"]["hits_per_s"]["unit"] == "hit/s"
    assert list(out)[-1] == "checks"
    assert out["checks"]["volumes_wrong"] == {"value": 0, "limit": 0}


def test_per_layer_reader_reads_the_run(tmp_path, cpu_devices):
    root = write_root(tmp_path, BENCH / "metrics")
    cell = run.load_cell(root, "t-cell")
    cfg = cell.driver.program(cell.config)
    from bench.lib import generate
    pool = [generate.make_job(cell.traffic, 1, 0, 6, 384)]
    cell.driver.warm_up(cfg, cell.shape)
    jobs, window_s = run.timed_jobs(cell.driver, cfg, pool, cell.shape,
                                    0.0)
    r = run.Run(cell, jobs, window_s, 1.0, "cpu")
    waste = run.read_metric(root, cell.per_layer[0], r)
    steps = jobs[0].lane_steps
    assert waste == pytest.approx(
        100 * (1 - jobs[0].ideal_lane_steps / steps))
    assert 0 < waste < 100
    for name in ("device_idle_share", "runner_other_ms_per_step",
                 "record_kernel_roofline"):
        assert run.read_metric(root, {"name": name}, r) is None


def _break(monkeypatch, fault):
    """Break the sweep engine's chunk runner underneath the harness."""
    import functools
    import importlib

    import jax
    import jax.numpy as jnp

    sweep = importlib.import_module("repro.cache.sweep")
    real = sweep._runner

    @functools.lru_cache(maxsize=None)
    def broken(cfg, unroll, n_shards=1):
        init, run_chunk, place = real(cfg, unroll, n_shards)

        @jax.jit
        def chunk(carry, blocks, valid):
            if fault == "state_unchanged":
                return carry, jnp.zeros(blocks.shape, bool)
            if fault == "half_left_out":
                half = jnp.arange(valid.shape[1]) < valid.shape[1] // 2
                return run_chunk(carry, blocks, valid & half[None, :])
            new, hits = run_chunk(carry, blocks, valid)
            st = new["stats"]
            return {**new, "stats": st._replace(
                hits=st.hits.at[0].add(1))}, hits
        return init, chunk, place

    monkeypatch.setattr(sweep, "_runner", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered"])
def test_broken_timed_path_is_not_correct(tmp_path, cpu_devices,
                                          monkeypatch, fault):
    root = write_root(tmp_path, BENCH / "metrics")
    _break(monkeypatch, fault)
    out = measure(root, cpu_devices)
    assert not out["correct"]
    assert out["checks"]["volumes_wrong"]["value"] > 0
    assert out["failed"] == out["checks"]["volumes_wrong"]["value"]


def _run(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH",)}
    env.update({"JAX_PLATFORMS": "cpu", **(env_extra or {})})
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "paper-assoc",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode == 3 and p.stdout == ""
    assert "no TPU" in p.stderr


def test_bare_benchmark_directory_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


def test_traced_window_records_one_span(tmp_path, cpu_devices, monkeypatch):
    """A traced run's profiler holds one window span, which closes with
    the window where the window is shorter than ``TRACE_S``."""
    from jax.profiler import ProfileData

    from bench.lib import generate, trace

    root = write_root(tmp_path / "root", BENCH / "metrics")
    cell = run.load_cell(root, "t-cell")
    cfg = cell.driver.program(cell.config)
    pool = [generate.make_job(cell.traffic, 5, 0, 6, 384)]
    cell.driver.warm_up(cfg, cell.shape)
    monkeypatch.setattr(run, "TRACE_S", 60.0)
    jobs, window_s = run.traced_jobs(cell.driver, cfg, pool, cell.shape,
                                     0.0, str(tmp_path / "tr"))
    assert len(jobs) == 1
    data = ProfileData.from_file(trace.find_xplane(str(tmp_path / "tr")))
    spans = [e for p in data.planes if p.name == trace.HOST_PLANE
             for ln in p.lines for e in ln.events
             if e.name == run.WINDOW_SPAN]
    assert len(spans) == 1
    assert 0 < (spans[0].end_ns - spans[0].start_ns) * 1e-9 < 30
