#!/usr/bin/env python3
"""The benchmark's controls: the plain reference put in the program's
place with one stated guarantee broken, checked as a run checks the
program. Each must come out not correct.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

For each seed and each control of the cell's driver (its ``CONTROLS``)
it builds the seed's jobs as a run does, takes every volume's counts
from the control, and prints the run's checks against the reference,
one JSON line each. It needs no accelerator: the controls run on the
host.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import run  # noqa: E402


def control_checks(cell: run.Cell, seed: int, control: str) -> dict:
    """The run's checks with every job's counts taken from ``control``."""
    pool = cell.driver.make_pool(cell, seed)
    jobs = [run.Job(i, run.reference_counts(cell, vols, control), 0, 0, 0,
                    0, {})
            for i, vols in enumerate(pool)]
    return run.compare(cell, pool, jobs)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    a = ap.parse_args(argv)
    cell = run.load_cell(ROOT, a.workload)
    for seed in a.seeds:
        for control in sorted(c for c in cell.driver.CONTROLS if c):
            t0 = time.perf_counter()
            checks = control_checks(cell, seed, control)
            print(json.dumps({
                "workload": a.workload, "seed": seed, "control": control,
                "correct": all(c["value"] <= c["limit"]
                               for c in checks.values()),
                "seconds": round(time.perf_counter() - t0, 3),
                "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
