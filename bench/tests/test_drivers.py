"""Drivers, the harness's one extension point, on the CPU at a tiny size.

The default driver makes the jobs and counts the harness made before it
had drivers; a deployment with new semantics (its own generator, request
columns, program call, count width and plain reference) runs through
``run.measure`` as new files alone, and a fault planted in its program
is caught; a driver that is missing, or a configuration its reference
cannot take, fails in set-up, before any job; the mining and producer
readers read the jobs' own ``streaming_stats()``."""

import json
import shutil
from pathlib import Path

import numpy as np
import pytest

from bench import control, run
from bench.lib import generate, reference
from tiny import TINY_CONFIG, write_root

BENCH = Path(__file__).resolve().parents[1]
TOY_TRAFFIC = {"write_share": 0.3, "sizes": [1, 2, 8, 32],
               "length_fracs": [1.0, 0.5, 0.75]}
TOY_SHAPE = {"volumes_per_job": 5, "length": 300, "pool_jobs": 2}


@pytest.fixture
def cpu_devices():
    import jax
    return jax.devices()


def _files(top: Path):
    """Every file under ``top`` but byte code, with its size and mtime."""
    return {p: (p.stat().st_size, p.stat().st_mtime_ns)
            for p in top.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.mark.parametrize("seed", [3, 2**31 + 11, 4140000901])
def test_default_driver_pool_is_generate_make_job(tmp_path, seed):
    root = write_root(tmp_path, BENCH / "metrics")
    cell = run.load_cell(root, "t-cell")
    assert Path(cell.driver.__file__).name == "block_sweep.py"
    shape = cell.shape
    got = cell.driver.make_pool(cell, seed)
    assert len(got) == shape["pool_jobs"]
    for j, vols in enumerate(got):
        want = generate.make_job(cell.traffic, seed, j,
                                 shape["volumes_per_job"],
                                 shape["nominal_length"])
        assert [(v.family, v.params, v.blocks.dtype, v.blocks.tobytes())
                for v in vols] == \
            [(v.family, v.params, v.blocks.dtype, v.blocks.tobytes())
             for v in want]


def test_default_driver_counts_are_the_reference(tmp_path):
    root = write_root(tmp_path, BENCH / "metrics")
    cell = run.load_cell(root, "t-cell")
    program = cell.driver.program(cell.config)
    pool = cell.driver.make_pool(cell, 2**31 + 3)
    cell.driver.warm_up(program, cell.shape)
    jobs, _ = run.timed_jobs(cell.driver, program, pool, cell.shape, 0.0)
    (job,) = jobs
    assert job.counts.shape == (len(pool[0]), 14)
    for row, v in zip(job.counts, pool[0]):
        assert row.tolist() == reference.simulate_flat(cell.config, v.blocks)
    assert job.requests == sum(len(v.blocks) for v in pool[0])
    assert job.stats["n_slabs"] == job.n_slabs


def _toy_root(tmp_path):
    """The tiny tree plus a block-log deployment added as files: its
    driver, a configuration naming it, its traffic and its cell."""
    root = write_root(tmp_path, BENCH / "metrics")
    bench = root / "bench"
    shutil.copy(Path(__file__).with_name("toy_log.py"),
                bench / "drivers" / "toy_log.py")
    (bench / "configs" / "toy.json").write_text(json.dumps(
        {"name": "toy", "driver": "toy_log", "block_bytes": 4096}))
    (bench / "traffic" / "log.json").write_text(json.dumps(TOY_TRAFFIC))
    (bench / "cells" / "toy-cell.json").write_text(json.dumps(TOY_SHAPE))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy", "source": "test",
                            "file": "bench/configs/toy.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "toy-cell", "config": "toy",
                              "traffic": "log", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_new_semantics_are_files_only(tmp_path, cpu_devices, monkeypatch):
    import jax
    import jax.numpy as jnp

    harness = _files(BENCH)
    root = _toy_root(tmp_path)
    cell = run.load_cell(root, "toy-cell")
    out = run.measure(root, cell, 2**31 + 5, 0.0, False, cpu_devices)
    assert out["correct"], out["checks"]
    assert out["attempted"] == TOY_SHAPE["volumes_per_job"]
    assert out["checks"]["count_gap_max"] == {"value": 0, "limit": 0}
    assert set(out["metrics"]) == {"req_per_s", "setup_s"}

    # the counts are the toy's own, four a volume, and they count
    pool = cell.driver.make_pool(cell, 7)
    program = cell.driver.program(cell.config)
    cell.driver.warm_up(program, cell.shape)
    jobs, _ = run.timed_jobs(cell.driver, program, pool, cell.shape, 0.0)
    assert jobs[0].counts.shape == (TOY_SHAPE["volumes_per_job"], 4)
    assert (jobs[0].counts[:, 2] > 0).all() and (jobs[0].counts[:, 1] > 0
                                                 ).all()

    # a fault planted in the toy's program side: each volume's first
    # write counted as a read
    real = cell.driver.program

    def broken(config):
        count = real(config)

        def first_write_read(op, size, valid):
            first = (op == 1) & (jnp.cumsum(op == 1, axis=1) == 1)
            return count(jnp.where(first, 0, op), size, valid)
        return jax.jit(first_write_read)
    monkeypatch.setattr(cell.driver, "program", broken)
    out = run.measure(root, cell, 2**31 + 5, 0.0, False, cpu_devices)
    assert not out["correct"]
    assert out["checks"]["volumes_wrong"]["value"] > 0

    assert _files(BENCH) == harness
    assert not (BENCH / "drivers" / "toy_log.py").exists()


def test_new_semantics_controls_through_the_driver(tmp_path):
    cell = run.load_cell(_toy_root(tmp_path), "toy-cell")
    checks = control.control_checks(cell, 3, "blocks_not_bytes")
    assert checks["volumes_wrong"]["value"] > 0
    assert control.control_checks(cell, 3, "")["volumes_wrong"][
        "value"] == 0


def test_missing_driver_fails_in_load_cell(tmp_path):
    root = write_root(tmp_path, BENCH / "metrics",
                      config={**TINY_CONFIG, "driver": "no_such_driver"})
    with pytest.raises(LookupError, match="no_such_driver"):
        run.load_cell(root, "t-cell")


def test_unsupported_configuration_fails_before_any_job(
        tmp_path, cpu_devices, monkeypatch):
    root = write_root(tmp_path, BENCH / "metrics",
                      config={**TINY_CONFIG, "policy": "fifo"})
    cell = run.load_cell(root, "t-cell")

    def no_job(*a, **k):
        raise AssertionError("set-up went on past the support check")
    for name in ("program", "make_pool", "warm_up", "run_job"):
        monkeypatch.setattr(cell.driver, name, no_job)
    with pytest.raises(ValueError, match="LRU"):
        run.measure(root, cell, 1, 0.0, False, cpu_devices)


def test_mining_and_producer_readers_read_streaming_stats(tmp_path):
    """The readers give the mean over the window's jobs of what the
    program's own ``streaming_stats()`` says for the same volumes, and
    nothing where a key is missing."""
    from repro.cache.sweep import sweep_streaming

    root = write_root(tmp_path, BENCH / "metrics")
    cell = run.load_cell(root, "t-cell")
    program = cell.driver.program(cell.config)
    pool = cell.driver.make_pool(cell, 11)
    cell.driver.warm_up(program, cell.shape)
    jobs = [run.timed_jobs(cell.driver, program, [p], cell.shape, 0.0)[0][0]
            for p in pool]
    own = [sweep_streaming(program, [v.blocks for v in p],
                           lane_width=cell.shape["lanes"],
                           chunk=cell.shape["chunk"]).streaming_stats()
           for p in pool]
    r = run.Run(cell, jobs, 1.0, 1.0, "cpu")

    def read(name):
        return run.read_metric(root, {"name": name}, r)
    for key in ("solo_passes", "fused_passes"):
        want = np.mean([s["mining"][key] for s in own])
        assert read(f"mine_{key.split('_')[0]}_passes_per_job") == want
    assert read("mine_solo_passes_per_job") > 0
    produce = sum(j.stats["pipeline"]["produce_s"] for j in jobs)
    assert [j.n_slabs for j in jobs] == [s["n_slabs"] for s in own]
    assert read("produce_ms_per_slab") == pytest.approx(
        1e3 * produce / sum(s["n_slabs"] for s in own))
    assert read("produce_ms_per_slab") > 0

    for j in jobs:
        j.stats = {}
    for name in ("mine_solo_passes_per_job", "mine_fused_passes_per_job",
                 "produce_ms_per_slab"):
        assert read(name) is None
        assert run.read_metric(
            root, {"name": name}, run.Run(cell, [], 1.0, 1.0, "cpu")) is None
