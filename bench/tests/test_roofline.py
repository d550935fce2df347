"""Work counts behind the kernels' roofline shares, by hand at both
cells' shapes, and the peak table."""

import json
from pathlib import Path

import pytest

from bench.lib import roofline

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def config(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


@pytest.mark.parametrize("name,nbytes", [
    # 4 ways x (5 fields + R=4 timestamps) x 4 B read, one way written,
    # one mining row (block, S=8 timestamps, count) read and written,
    # four scalars read and written
    ("mithril-lru-paper", 4 * 36 + 36 + 2 * 40 + 2 * 16),
    ("mithril-amp-lru-paper", 4 * 36 + 36 + 2 * 40 + 2 * 16),
])
def test_record_event_bytes(name, nbytes):
    w = roofline.record_event(config(name))
    assert w.bytes == nbytes == 292
    assert w.ops == 2 * 4 + 2


@pytest.mark.parametrize("name,ops,nbytes", [
    # 1,250 rows x window 100 x S=8; 1,250 x 9 words in, 2-bit codes out
    ("mithril-lru-paper", 1250 * 100 * 8, 1250 * 9 * 4 + 1250 * 100 / 4),
    ("mithril-amp-lru-paper", 1250 * 100 * 8, 1250 * 9 * 4 + 1250 * 100 / 4),
])
def test_mine_pass(name, ops, nbytes):
    w = roofline.mine_pass(config(name))
    assert (w.ops, w.bytes) == (ops, nbytes)


def test_least_time_takes_the_larger_bound():
    p = roofline.peaks("TPU v5 lite")
    w = roofline.mine_pass(config("mithril-lru-paper"))
    assert roofline.least_time(w, p) == pytest.approx(76250 / 819e9)
    compute = roofline.Work(393e12, 1.0)
    assert roofline.least_time(compute, p) == pytest.approx(1.0)
    assert roofline.least_time(w.scaled(3), p) == pytest.approx(
        3 * 76250 / 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 giant")
