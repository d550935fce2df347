"""The plain reference against the program's serial simulator on the CPU.

The reference decides ``correct``; these tests show that it computes what
the program computes, on the benchmark's own traffic and on tables small
enough that mining, the prefetch table's FIFO and eviction, and the
second-chance rule all run many times."""

import dataclasses

import numpy as np
import pytest

from bench.lib import generate, reference
from bench.drivers.block_sweep import sim_config

from tiny import TINY_CONFIG, TRAFFIC


def program_counts(config, blocks):
    from repro.cache import simulate

    st = simulate(sim_config(config), blocks).stats
    return [int(st.requests), int(st.hits), *map(int, st.pf_issued),
            *map(int, st.pf_used), *map(int, st.pf_evicted_unused)]


def bench_config(name):
    import json
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    return json.loads((root / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("config", [
    TINY_CONFIG, bench_config("mithril-amp-lru-paper"),
    bench_config("mithril-lru-paper")], ids=["tiny", "amp-paper", "paper"])
@pytest.mark.parametrize("seed", [3, 2**31 + 11])
def test_reference_matches_program(config, seed):
    for v in generate.make_job(TRAFFIC, seed, 0, 5, 600):
        assert reference.simulate(config, v.blocks).flat() == \
            program_counts(config, v.blocks), (v.family, v.params)


def test_tiny_tables_mine_and_prefetch():
    """The tiny configuration exercises what the comparison must cover."""
    mined = issued = 0
    orig = reference._Mithril.mine

    def count(self):
        nonlocal mined
        mined += 1
        return orig(self)

    reference._Mithril.mine = count
    try:
        for v in generate.make_job(TRAFFIC, 3, 0, 6, 600):
            c = reference.simulate(TINY_CONFIG, v.blocks)
            issued += c.pf_issued[reference.MITHRIL]
    finally:
        reference._Mithril.mine = orig
    assert mined > 10 and issued > 5


def test_unsupported_configuration_is_refused():
    with pytest.raises(ValueError):
        reference.simulate(dict(TINY_CONFIG, policy="fifo"), np.zeros(4))
    with pytest.raises(ValueError):
        reference.simulate(TINY_CONFIG, np.zeros(4), control="bogus")


def test_mix32_matches_program():
    import jax.numpy as jnp
    from repro.core.hashindex import mix32

    keys = np.array([0, 1, 7, 2**20 + 3, 2**30 - 1, 2**31 - 1, -1],
                    np.int32)
    got = np.asarray(mix32(jnp.asarray(keys))).astype(np.int64) & 0xFFFFFFFF
    assert [reference.mix32(int(k)) for k in keys] == got.tolist()


def test_sim_config_takes_every_field():
    cfg = sim_config(TINY_CONFIG)
    assert cfg.capacity == 32 and cfg.mithril.mine_rows == 8
    assert cfg.amp.n_streams == 8 and cfg.use_amp
    assert dataclasses.asdict(cfg)["mithril"]["max_pairs"] == 10


@pytest.mark.parametrize("control", ["sixteen_way_sets",
                                     "mining_per_slab"])
def test_control_run_is_not_correct(tmp_path, control):
    """The controls, checked as a run checks the program, fail."""
    from pathlib import Path

    from bench import run
    from bench.control import control_checks
    from tiny import write_root

    root = write_root(tmp_path, Path(run.BENCH) / "metrics")
    cell = run.load_cell(root, "t-cell")
    cell.shape = dict(cell.shape, chunk=64)
    checks = control_checks(cell, 3, control)
    assert checks["volumes_wrong"]["value"] > 0
    assert checks["count_gap_max"]["value"] > 0
