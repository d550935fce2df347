"""Peaks of the chips the benchmark runs on, and the work the two MITHRIL
kernels' jobs need, counted from the configuration's shapes.

The counts are of the work a request (or a mining pass) needs, not of
what a kernel's block layout happens to move, so a leaner kernel that
does the same work can approach 100% of its roofline and never exceed it.
Nothing here imports the program.
"""

from __future__ import annotations

from typing import Dict, NamedTuple

I32 = 4     # bytes

# Published peaks per chip, keyed by JAX's ``device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 393 TOP/s
# int8, 16 GB HBM at 819 GB/s). The kernels here do int32 vector work;
# no int32 peak is published, so the int8 peak, the highest published
# operation rate, bounds their operations from above.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"ops_per_s": 393e12, "bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


class Work(NamedTuple):
    ops: float
    bytes: float

    def __add__(self, other):
        return Work(self.ops + other.ops, self.bytes + other.bytes)

    def scaled(self, n: float) -> "Work":
        return Work(self.ops * n, self.bytes * n)


def least_time(work: Work, peak: Dict[str, float]) -> float:
    """Seconds the chip needs at least: the larger of the two bounds."""
    return max(work.ops / peak["ops_per_s"], work.bytes / peak["bytes_per_s"])


def record_event(m: dict) -> Work:
    """One recording event (a demand miss) of the MITHRIL record step.

    It reads the probed recording bucket's ``rec_ways`` ways (key, count,
    age, location, mining row and R timestamps each), writes one way
    back, reads and writes one mining-table row (block, S timestamps,
    count), and reads and writes the lane's scalars (block, enable,
    fill, timestamp). Operations: a compare per way to probe, a compare
    per way to pick the victim, one timestamp store and the migrate test.
    """
    way = (5 + m["min_support"]) * I32
    row = (2 + m["max_support"]) * I32
    scalars = 4 * I32
    nbytes = m["rec_ways"] * way + way + 2 * row + 2 * scalars
    ops = 2 * m["rec_ways"] + 2
    return Work(ops, nbytes)


def mine_window(m: dict) -> int:
    if m["max_window"]:
        return min(m["max_window"], m["mine_rows"] - 1)
    return min(m["mine_rows"] - 1, m["lookahead"])


def mine_pass(m: dict) -> Work:
    """The pairwise association check of one lane's full mining table.

    It reads every row's S timestamps and its count once, and yields an
    association code (none, weak or strong: 2 bits) for each row and each
    of the ``window`` rows after it. Operations: one per aligned
    timestamp pair compared.
    """
    n, s, w = m["mine_rows"], m["max_support"], mine_window(m)
    nbytes = n * (s + 1) * I32 + n * w * 2 / 8
    return Work(n * w * s, nbytes)
