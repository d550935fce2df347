"""The write-back driver ``rw_sweep`` on the CPU at a tiny size: a cell of
it runs through ``run.measure`` and comes out correct, each of its
controls comes out not correct, a program without a write-back cache
fails in set-up, and its two readers read nothing where the program
keeps no such span or counter."""

import dataclasses
import json
from pathlib import Path

import numpy as np
import pytest

from bench import control, run
from bench.drivers import rw_sweep
from tiny import TINY_CONFIG, write_root
from test_scopes import HLO, _run, _trace_file

BENCH = Path(__file__).resolve().parents[1]
CONFIG = {**TINY_CONFIG, "name": "wb", "driver": "rw_sweep",
          "write_back": True, "use_amp": False, "capacity": 32, "ways": 16,
          "lookahead": 50, "pf_buckets": 64}
TRAFFIC = {"extent_blocks": 16, "volumes": [
    {"kind": "groups", "family": "midfreq", "length_frac": 1.0,
     "write_share": 0.6, "sizes": [[1, 0.8], [2, 0.1], [16, 0.1]],
     "params": {"n_groups": 20, "group_size": 4, "reuse": 8, "spread": 3}},
    {"kind": "hot", "family": "finite_zipf", "length_frac": 0.8,
     "write_share": 0.9, "sizes": [[1, 0.7], [4, 0.3]],
     "params": {"alpha": 0.9, "catalog": 256}},
    {"kind": "log", "family": "seq", "length_frac": 0.6,
     "write_share": 0.8, "sizes": [[8, 0.5], [16, 0.5]],
     "params": {"n_streams": 4, "run_len": 16, "skip_prob": 0.05}}]}
SHAPE = {"lanes": 4, "volumes_per_job": 6, "nominal_length": 512,
         "chunk": 64, "pool_jobs": 2}


@pytest.fixture
def cpu_devices():
    import jax
    return jax.devices()


def _wb_root(tmp_path):
    """The tiny tree plus a write-back cell ``wb-cell`` added as files."""
    root = write_root(tmp_path, BENCH / "metrics")
    bench = root / "bench"
    (bench / "configs" / "wb.json").write_text(json.dumps(CONFIG))
    (bench / "traffic" / "rw.json").write_text(json.dumps(TRAFFIC))
    (bench / "cells" / "wb-cell.json").write_text(json.dumps(SHAPE))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "wb", "source": "test",
                            "file": "bench/configs/wb.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "wb-cell", "config": "wb",
                              "traffic": "rw", "chips": 1, "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


@pytest.mark.parametrize("seed", [2**31 + 29, 4160000901])
def test_write_back_cell_is_correct(tmp_path, cpu_devices, seed):
    root = _wb_root(tmp_path)
    cell = run.load_cell(root, "wb-cell")
    assert Path(cell.driver.__file__).name == "rw_sweep.py"
    out = run.measure(root, cell, seed, 0.0, False, cpu_devices)
    assert out["correct"], out["checks"]
    assert out["attempted"] == SHAPE["volumes_per_job"]
    assert set(out["metrics"]) == {"req_per_s", "setup_s"}

    program = cell.driver.program(cell.config)
    assert program.write_back
    pool = cell.driver.make_pool(cell, seed)
    jobs, _ = run.timed_jobs(cell.driver, program, pool, cell.shape, 0.0)
    counts = jobs[0].counts
    assert counts.shape == (SHAPE["volumes_per_job"], 18)
    # writes, write-backs and MITHRIL's prefetches all counted
    assert (counts[:, 14] > 0).all() and (counts[:, 16] > 0).all()
    assert counts[:, 3].sum() > 0
    assert jobs[0].stats["pipeline"]["staged_bytes"] == \
        jobs[0].n_slabs * SHAPE["chunk"] * SHAPE["lanes"] * 6


def test_pool_is_seeded_and_shifts_whole_volumes():
    cell = run.Cell("c", 1, CONFIG, rw_sweep, TRAFFIC, SHAPE, [], [])
    a = rw_sweep.make_pool(cell, 5)
    b = rw_sweep.make_pool(cell, 5)
    c = rw_sweep.make_pool(cell, 6)
    for va, vb, vc in zip(a[0], b[0], c[0]):
        assert va["blocks"].tobytes() == vb["blocks"].tobytes()
        # another seed moves the addresses, not the sizes or the ops
        assert va["blocks"].tobytes() != vc["blocks"].tobytes()
        np.testing.assert_array_equal(va["writes"], vc["writes"])
        np.testing.assert_array_equal(np.diff(va["blocks"]) == 1,
                                      np.diff(vc["blocks"]) == 1)
        assert va["blocks"].min() >= 0 and va["blocks"].max() < 2**30


@pytest.mark.parametrize("alpha", [0.9, 1.2])
def test_finite_zipf_follows_the_law_over_its_catalog(alpha):
    """Rank ``k - 1`` comes with probability proportional to
    ``k ** -alpha`` over the whole catalog, below 1 as above it, with no
    mass piled on the last rank; a seed gives the same draws."""
    catalog, n = 64, 400_000
    draws = rw_sweep.finite_zipf(n, catalog=catalog, alpha=alpha, seed=3)
    assert draws.dtype == np.int32
    assert draws.min() >= 0 and draws.max() < catalog
    law = np.arange(1, catalog + 1, dtype=np.float64) ** -alpha
    law /= law.sum()
    seen = np.bincount(draws, minlength=catalog) / n
    assert (np.abs(seen - law) <= 4 * np.sqrt(law / n)).all()
    np.testing.assert_array_equal(
        draws, rw_sweep.finite_zipf(n, catalog=catalog, alpha=alpha, seed=3))


def test_the_cells_traffic_draws_every_family_it_names():
    traffic = json.loads((BENCH / "traffic" / "msr.json").read_text())
    hot = [e for e in traffic["volumes"] if e["family"] == "finite_zipf"]
    assert [e["params"] for e in hot] == [{"alpha": 0.9, "catalog": 65536}]
    for e in traffic["volumes"]:
        assert e["family"] in rw_sweep.FAMILIES


@pytest.mark.parametrize("name", sorted(c for c in rw_sweep.CONTROLS if c))
def test_each_control_is_not_correct(tmp_path, name):
    cell = run.load_cell(_wb_root(tmp_path), "wb-cell")
    checks = control.control_checks(cell, 3, name)
    assert checks["volumes_wrong"]["value"] > 0, name


def test_the_reference_as_a_control_is_correct(tmp_path):
    cell = run.load_cell(_wb_root(tmp_path), "wb-cell")
    assert control.control_checks(cell, 3, "")["volumes_wrong"][
        "value"] == 0


def test_program_without_write_back_fails_before_any_job(
        tmp_path, cpu_devices, monkeypatch):
    """A program whose ``SimConfig`` has no ``write_back`` (as before the
    write-back cache) is refused by ``check``, first thing in set-up."""
    import repro.cache

    @dataclasses.dataclass(frozen=True)
    class ReadOnlyConfig:
        capacity: int = 4096

    monkeypatch.setattr(repro.cache, "SimConfig", ReadOnlyConfig)
    with pytest.raises(ValueError, match="write_back"):
        rw_sweep.check(CONFIG)
    cell = run.load_cell(_wb_root(tmp_path), "wb-cell")

    def no_job(*a, **k):
        raise AssertionError("set-up went on past the support check")
    for name in ("program", "make_pool", "warm_up", "run_job"):
        monkeypatch.setattr(cell.driver, name, no_job)
    with pytest.raises(ValueError, match="write_back"):
        run.measure(tmp_path, cell, 1, 0.0, False, cpu_devices)


def test_unsupported_configurations_are_refused():
    with pytest.raises(ValueError, match="write-back"):
        rw_sweep.check_supported({**CONFIG, "write_back": False})
    with pytest.raises(ValueError, match="AMP"):
        rw_sweep.check_supported({**CONFIG, "use_amp": True})


def _reader(name):
    import importlib.util
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"t_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_staged_bytes_reader(tmp_path):
    cell = run.load_cell(_wb_root(tmp_path), "wb-cell")
    jobs = [run.Job(0, np.zeros((1, 18)), 1, n, 0, 0,
                    {"pipeline": {"staged_bytes": 1536 * n}})
            for n in (2, 3)]
    r = run.Run(cell, jobs, 1.0, 1.0, "cpu")
    read = _reader("staged_bytes_per_slab").read
    assert read(r) == 1536
    jobs[1].stats = {"pipeline": {}}
    assert read(r) is None
    assert read(run.Run(cell, [], 1.0, 1.0, "cpu")) is None


def test_writeback_reader_reads_the_scope(tmp_path):
    """By hand: the scope test's trace against a runner whose access
    fusion carries the ``writeback`` scope reads that fusion's own time
    per record-kernel launch; against one without the scope, and with no
    trace, it reads nothing."""
    mod = _reader("writeback_ms_per_step")
    summary = _trace_file(tmp_path)
    scoped = HLO.replace("/access/vmap()/add", "/access/vmap(writeback)/add")
    assert mod.ms_per_step(summary, scoped) == pytest.approx(10e-6)
    assert mod.ms_per_step(summary, HLO) is None
    assert mod.read(_run(None)) is None
    assert mod.in_scope("jit(f)/while/body/prefetch/vmap(writeback)/sub")
    assert not mod.in_scope("jit(f)/while/body/access/vmap()/add")


def test_writeback_reader_reads_nothing_for_a_read_only_cell(tmp_path):
    mod = _reader("writeback_ms_per_step")
    summary = _trace_file(tmp_path)
    cell = run.load_cell(write_root(tmp_path, BENCH / "metrics"), "t-cell")
    r = run.Run(cell, [], 1.0, 1.0, "cpu", summary)
    assert mod.read(r) is None
