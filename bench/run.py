#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``: the
deployment under test, its settings and guarantees), a traffic mix
(``bench/traffic/<traffic>.json``) and its job shape
(``bench/cells/<cell>.json``). The configuration names a driver
(``bench/drivers/<driver>.py``, ``block_sweep`` where it names none),
which owns what is particular to its kind of deployment: the program
call, the jobs made from ``--seed``, the warm-up, the counts a job hands
back and the plain reference they are compared with
(``bench/drivers/__init__.py`` states the contract).

Set-up checks that the driver's reference can take the configuration,
makes the seed's jobs, loads the compiled programs from the persistent
compilation cache (``<checkout>/.jax_cache`` unless
``JAX_COMPILATION_CACHE_DIR`` says otherwise) and warms up the cell's
shapes. The window then runs whole jobs back to back until the first
job that ends after ``--seconds``; the jobs cycle through the seed's
distinct jobs. No job may compile.

With ``--trace 0`` the result line carries the cell's end-to-end metrics;
with ``--trace 1`` the JAX profiler records the window's first
``TRACE_S`` seconds and the line carries the per-layer metrics. Each
metric is computed by ``bench/metrics/<name>.py``. Afterwards every
job's per-volume counts are compared with the driver's plain reference;
``correct`` holds when no count differs.

The run needs a TPU with as many chips as the cell asks for; without one
it exits with code 3 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse             # noqa: E402
import dataclasses          # noqa: E402
import importlib.util       # noqa: E402
import json                 # noqa: E402
import shutil               # noqa: E402
import sys                  # noqa: E402
import tempfile             # noqa: E402
from pathlib import Path    # noqa: E402
from types import ModuleType  # noqa: E402
from typing import Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np          # noqa: E402

from bench import drivers   # noqa: E402
# tests/test_sweep_tracing.py imports the sweep's configuration builder
# from here
from bench.drivers.block_sweep import sim_config  # noqa: E402,F401

WINDOW_SPAN = "bench_window"        # host annotation around the traced span
TRACE_S = 1.0                       # seconds of the window a trace records
REFERENCE_WORKERS = 8               # host processes for the reference


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


@dataclasses.dataclass
class Cell:
    """Everything one cell of ``BENCHMARK.json`` names, loaded."""

    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    driver: ModuleType      # bench/drivers/<config's driver>.py
    traffic: dict           # bench/traffic/<traffic>.json
    shape: dict             # bench/cells/<name>.json
    end_to_end: List[dict]  # the cell's end-to-end metric entries
    per_layer: List[dict]   # the cell's per-layer metric entries


@dataclasses.dataclass
class Job:
    """One timed job and what the driver's ``run_job`` handed back."""

    pool_index: int
    counts: np.ndarray      # (volumes, k) per-volume counts
    requests: int
    n_slabs: int
    lane_steps: int
    ideal_lane_steps: int
    stats: dict             # the program's own counters and spans


@dataclasses.dataclass
class Run:
    """What the metric readers see."""

    cell: Cell
    jobs: List[Job]
    window_s: float         # host seconds from the first job's start
    setup_s: float
    device_kind: str
    trace: Optional[object] = None      # lib.trace.Summary when traced


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` and its files."""
    spec = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    bench = root / "bench"
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    return Cell(
        name=name, chips=int(w["chips"]), config=config,
        driver=drivers.load(root, config),
        traffic=json.loads(
            (bench / "traffic" / f"{w['traffic']}.json").read_text()),
        shape=json.loads((bench / "cells" / f"{name}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def read_metric(root: Path, entry: dict, run: Run) -> Optional[float]:
    """Call ``bench/metrics/<name>.py``'s ``read(run)``."""
    path = root / "bench" / "metrics" / f"{entry['name']}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{entry['name'].replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    value = mod.read(run)
    return None if value is None else float(value)


def check_chips(chips: int):
    """The TPU devices the cell runs on; raises :class:`NoChip`."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def timed_jobs(driver, program, pool, shape: dict, seconds: float):
    """Whole jobs of ``driver.run_job`` back to back until the first that
    ends after ``seconds``; returns (jobs, wall seconds)."""
    from jax.profiler import TraceAnnotation

    jobs: List[Job] = []
    t0 = time.perf_counter()
    k = 0
    while True:
        i = k % len(pool)
        with TraceAnnotation("job"):
            out = driver.run_job(program, pool[i], shape)
        compiles = out.pop("compiles")
        if compiles:
            raise RuntimeError(f"job {k} compiled {compiles} "
                               "programs inside the window")
        jobs.append(Job(i, **out))
        k += 1
        if time.perf_counter() - t0 >= seconds:
            return jobs, time.perf_counter() - t0


def traced_jobs(driver, program, pool, shape: dict, seconds: float,
                trace_dir: str):
    """:func:`timed_jobs` with the profiler recording the window's first
    ``TRACE_S`` seconds (or the whole window, where it is shorter) under
    the host span ``WINDOW_SPAN``. A span and not the whole window: the
    device trace holds every operation of every scan step, 200,000 to
    650,000 events a second; the profiler drops what passes 2 GB, and
    stopping and reducing even a 2 s span took minutes."""
    import threading

    import jax
    from jax.profiler import TraceAnnotation

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0            # spans, not every Python call
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    window_done = threading.Event()
    failed: List[BaseException] = []

    def span():
        try:
            with TraceAnnotation(WINDOW_SPAN):
                window_done.wait(TRACE_S)
        finally:
            try:
                jax.profiler.stop_trace()
            except BaseException as e:      # re-raised by the caller
                failed.append(e)

    stopper = threading.Thread(target=span, name="bench-trace")
    stopper.start()
    try:
        out = timed_jobs(driver, program, pool, shape, seconds)
    finally:
        window_done.set()
        stopper.join()
    if failed:
        raise failed[0]
    return out


def reference_counts(cell: Cell, volumes, control: str = "") -> np.ndarray:
    """(volumes, k) counts of the driver's plain reference (of
    ``control`` when given, a key of the driver's ``CONTROLS``), one
    volume per task on a pool of host processes that import nothing of
    JAX (spawned, never forked from a process that holds the chip)."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    n = len(volumes)
    workers = max(1, min(REFERENCE_WORKERS, n))
    with ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn")) as ex:
        rows = ex.map(drivers.reference_row, [cell.driver.__file__] * n,
                      [cell.config] * n, [cell.shape] * n, volumes,
                      [control] * n)
        return np.asarray(list(rows), np.int64)


def compare(cell: Cell, pool, jobs: List[Job]):
    """The checks: per-volume counts of every job against the reference."""
    due = sorted({j.pool_index for j in jobs})
    flat = reference_counts(cell, [v for i in due for v in pool[i]])
    want: Dict[int, np.ndarray] = {}
    for k, i in enumerate(due):
        want[i] = flat[k * len(pool[i]): (k + 1) * len(pool[i])]
    wrong, gap = 0, 0
    for j in jobs:
        diff = np.abs(j.counts - want[j.pool_index])
        wrong += int((diff.max(axis=1) > 0).sum())
        gap = max(gap, int(diff.max()))
    return {"volumes_wrong": {"value": wrong, "limit": 0},
            "count_gap_max": {"value": gap, "limit": 0}}


def measure(root: Path, cell: Cell, seed: int, seconds: float, trace: bool,
            devices, t_start: float = T_START) -> dict:
    """Set-up, window, metrics and checks of one run; the result line."""
    import jax
    from jax.profiler import TraceAnnotation

    from bench.lib import trace as trace_lib
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    driver, shape = cell.driver, cell.shape
    driver.check(cell.config)
    program = driver.program(cell.config)
    with TraceAnnotation("generate"):
        pool = driver.make_pool(cell, seed)
    driver.warm_up(program, shape)
    setup_s = time.perf_counter() - t_start

    summary = None
    if not trace:
        jobs, window_s = timed_jobs(driver, program, pool, shape, seconds)
    else:
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            jobs, window_s = traced_jobs(driver, program, pool, shape,
                                         seconds, tmp)
            summary = trace_lib.reduce(trace_lib.find_xplane(tmp),
                                       WINDOW_SPAN, len(devices),
                                       skip_names=("job", "readback"))
        finally:
            shutil.rmtree(tmp, ignore_errors=True)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)

    run = Run(cell, jobs, window_s, setup_s, devices[0].device_kind, summary)
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for e in entries:
        value = read_metric(root, e, run)
        if value is not None:
            metrics[e["name"]] = {"value": value, "unit": e["unit"]}
    checks = compare(cell, pool, jobs)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": int(peak)}
    out = {"correct": all(c["value"] <= c["limit"] for c in checks.values()),
           "attempted": sum(len(j.counts) for j in jobs),
           "failed": checks["volumes_wrong"]["value"],
           "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = trace_lib.breakdown(summary)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    cell = load_cell(ROOT, a.workload)
    try:
        devices = check_chips(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    out = measure(ROOT, cell, a.seed, a.seconds, bool(a.trace), devices)
    for name, c in out["checks"].items():
        print(f"check {name}={c['value']} limit={c['limit']}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
