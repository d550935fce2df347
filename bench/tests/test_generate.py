"""Traffic generation: seeded, and the same sizes for every seed."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench.lib import generate

from tiny import TRAFFIC

TRAFFIC_DIR = Path(__file__).resolve().parents[1] / "traffic"


def test_same_seed_same_volumes():
    a = generate.make_job(TRAFFIC, 2**31 + 5, 1, 7, 500)
    b = generate.make_job(TRAFFIC, 2**31 + 5, 1, 7, 500)
    assert all(np.array_equal(x.blocks, y.blocks) for x, y in zip(a, b))


def test_seeds_change_contents_not_sizes():
    a = generate.make_job(TRAFFIC, 1, 0, 24, 1000)
    b = generate.make_job(TRAFFIC, 2, 0, 24, 1000)
    assert [len(v.blocks) for v in a] == [len(v.blocks) for v in b]
    assert [v.family for v in a] == [v.family for v in b]
    assert [v.params for v in a] == [v.params for v in b]
    assert any(not np.array_equal(x.blocks, y.blocks) for x, y in zip(a, b))


def test_seeds_shift_each_volume_whole():
    """The seed moves each volume by an offset of its own and changes
    nothing else, so every seed is the same work."""
    a = generate.make_job(TRAFFIC, 1, 0, 10, 1000)
    b = generate.make_job(TRAFFIC, 2**31 + 77, 0, 10, 1000)
    shifts = set()
    for x, y in zip(a, b):
        d = (y.blocks.astype(np.int64) - x.blocks) % generate.ADDRESS_SPACE
        assert len(set(d.tolist())) == 1
        shifts.add(int(d[0]))
    assert len(shifts) == len(a)
    c = generate.make_job(TRAFFIC, 1, 1, 10, 1000)
    assert not np.array_equal(a[0].blocks, c[0].blocks)


def test_slots_cycle_through_the_entries():
    vols = generate.make_job(TRAFFIC, 3, 0, 2 * len(TRAFFIC["volumes"]) + 1,
                             1000)
    want = [e["family"] for e in TRAFFIC["volumes"]]
    assert [v.family for v in vols] == want * 2 + want[:1]
    assert [len(v.blocks) for v in vols[:len(want)]] == [
        int(1000 * e["length_frac"]) for e in TRAFFIC["volumes"]]


def test_unknown_family_is_refused():
    with pytest.raises(ValueError):
        generate.make_job({"volumes": [{"family": "bogus", "params": {},
                                        "length_frac": 1.0}]}, 1, 0, 1, 10)


@pytest.mark.parametrize("path", sorted(TRAFFIC_DIR.glob("*.json")),
                         ids=lambda p: p.stem)
def test_copied_generators_match_the_program(path):
    """Each family at each parameter set of a traffic file is what the
    program's own generator (traces/synthetic.py) makes."""
    from repro.traces import synthetic

    for e in json.loads(path.read_text())["volumes"]:
        fn = generate.BUILDERS[e["family"]]
        want = getattr(synthetic, fn.__name__)(300, seed=9, **e["params"])
        assert np.array_equal(fn(300, seed=9, **e["params"]), want), e
