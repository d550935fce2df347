#!/usr/bin/env python3
"""The benchmark: one cell of ``BENCHMARK.json``, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``: the
simulated cache and its MITHRIL and AMP settings), a traffic mix
(``bench/traffic/<traffic>.json``) and its job shape
(``bench/cells/<cell>.json``: lanes, volumes per job, nominal volume
length, chunk, and how many distinct jobs the seed makes).

Set-up generates the jobs' volumes on the host from ``--seed``, loads the
sweep engine's compiled programs from the persistent compilation cache
(``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` says
otherwise) and warms the cell's ``(chunk, lanes)`` runner and the lane
reset on a small job. The window then runs whole jobs back to back, each
one ``sweep_streaming`` call over the job's volumes through the cell's
recycled lanes, until the first job that ends after ``--seconds``; the
jobs cycle through the seed's distinct jobs. No job may compile.

With ``--trace 0`` the result line carries the cell's end-to-end metrics;
with ``--trace 1`` the JAX profiler records the window's first
``TRACE_S`` seconds and the line carries the per-layer metrics. Each
metric is computed by ``bench/metrics/<name>.py``. Afterwards every
job's per-volume counts are compared with the plain reference
(``bench/lib/reference.py``); ``correct`` holds when no count differs.

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
from typing import Dict, List, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np          # noqa: E402

from bench.lib import generate, reference  # noqa: E402

WINDOW_SPAN = "bench_window"        # host annotation around the traced span
TRACE_S = 1.0                       # seconds of the window a trace records
REFERENCE_WORKERS = 8               # host processes for the reference
# Slabs the streaming engine stages and dispatches ahead of the device:
# at ~0.32 s of device work a slab in both cells, some 8 s, so that a
# host that stands still for a few seconds leaves the chip fed. Each
# slab in flight holds its own output carry on the device (~97 MB).
RING_DEPTH = 24


class NoChip(RuntimeError):
    """The machine lacks the accelerator the cell asks for."""


@dataclasses.dataclass
class Cell:
    """Everything one cell of ``BENCHMARK.json`` names, loaded."""

    name: str
    chips: int
    config: dict            # bench/configs/<config>.json
    traffic: dict           # bench/traffic/<traffic>.json
    shape: dict             # bench/cells/<name>.json
    end_to_end: List[dict]  # the cell's end-to-end metric entries
    per_layer: List[dict]   # the cell's per-layer metric entries


@dataclasses.dataclass
class Job:
    """One timed ``sweep_streaming`` call and what it handed back."""

    pool_index: int
    counts: np.ndarray      # (volumes, 14) per-volume counts
    requests: int
    n_slabs: int
    lane_steps: int
    ideal_lane_steps: int


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
    return Cell(
        name=name, chips=int(w["chips"]),
        config=json.loads((root / configs[w["config"]]["file"]).read_text()),
        traffic=json.loads(
            (bench / "traffic" / f"{w['traffic']}.json").read_text()),
        shape=json.loads((bench / "cells" / f"{name}.json").read_text()),
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)])


def sim_config(config: dict):
    """The program's ``SimConfig`` for a configuration file, whose keys
    are the fields of ``SimConfig``, ``MithrilConfig`` and ``AmpConfig``."""
    from repro.cache import SimConfig
    from repro.cache.amp import AmpConfig
    from repro.core import MithrilConfig

    def pick(cls):
        return {f.name: config[f.name] for f in dataclasses.fields(cls)
                if f.name in config}

    return SimConfig(**{k: v for k, v in pick(SimConfig).items()
                        if k not in ("mithril", "amp", "pg", "learned")},
                     mithril=MithrilConfig(**pick(MithrilConfig)),
                     amp=AmpConfig(**pick(AmpConfig)))


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


def counts_of(stats) -> np.ndarray:
    """(volumes, 14): requests, hits, then issued, used and evicted
    unused per prefetch source."""
    return np.concatenate(
        [np.asarray(stats.requests)[:, None], np.asarray(stats.hits)[:, None],
         np.asarray(stats.pf_issued), np.asarray(stats.pf_used),
         np.asarray(stats.pf_evicted_unused)], axis=1).astype(np.int64)


def run_job(cfg, volumes, shape: dict):
    from repro.cache.sweep import sweep_streaming

    return sweep_streaming(cfg, [v.blocks for v in volumes],
                           lane_width=shape["lanes"], chunk=shape["chunk"],
                           ring_depth=RING_DEPTH)


def warm_up(cfg, shape: dict) -> None:
    """Run the cell's ``(chunk, lanes)`` runner and the lane reset once:
    ``lanes + 1`` one-slab volumes, so one lane is recycled."""
    chunk, lanes = shape["chunk"], shape["lanes"]
    blocks = np.arange(chunk, dtype=np.int32)
    out = run_job(cfg, [generate.Volume("warm", {}, blocks + i * chunk)
                        for i in range(lanes + 1)], shape)
    counts_of(out.result.stats)


def timed_jobs(cfg, pool, shape: dict, seconds: float):
    """Whole jobs back to back until the first that ends after
    ``seconds``; returns (jobs, wall seconds)."""
    from jax.profiler import TraceAnnotation

    jobs: List[Job] = []
    t0 = time.perf_counter()
    k = 0
    while True:
        i = k % len(pool)
        with TraceAnnotation("job"):
            out = run_job(cfg, pool[i], shape)
        with TraceAnnotation("readback"):
            counts = counts_of(out.result.stats)
        if out.result.compiles:
            raise RuntimeError(f"job {k} compiled {out.result.compiles} "
                               "programs inside the window")
        st = out.streaming_stats()
        jobs.append(Job(i, counts, int(counts[:, 0].sum()), out.n_slabs,
                        st["lane_steps"], st["ideal_lane_steps"]))
        k += 1
        if time.perf_counter() - t0 >= seconds:
            return jobs, time.perf_counter() - t0


def traced_jobs(cfg, pool, shape: dict, seconds: float, trace_dir: str):
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
        out = timed_jobs(cfg, pool, shape, seconds)
    finally:
        window_done.set()
        stopper.join()
    if failed:
        raise failed[0]
    return out


def reference_counts(config: dict, volumes, control: str = "",
                     slab: int = 256) -> np.ndarray:
    """(volumes, 14) reference counts (of ``control`` when given, see
    ``reference.CONTROLS``), one volume per task on a pool of host
    processes that import nothing of JAX (spawned, never forked from a
    process that holds the chip)."""
    import multiprocessing
    from concurrent.futures import ProcessPoolExecutor

    workers = max(1, min(REFERENCE_WORKERS, len(volumes)))
    with ProcessPoolExecutor(
            workers, mp_context=multiprocessing.get_context("spawn")) as ex:
        n = len(volumes)
        rows = ex.map(reference.simulate_flat, [config] * n,
                      [v.blocks for v in volumes], [control] * n, [slab] * n)
        return np.asarray(list(rows), np.int64)


def compare(config: dict, pool, jobs: List[Job]):
    """The checks: per-volume counts of every job against the reference."""
    due = sorted({j.pool_index for j in jobs})
    flat = reference_counts(config, [v for i in due for v in pool[i]])
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
    shape = cell.shape
    cfg = sim_config(cell.config)
    with TraceAnnotation("generate"):
        pool = [generate.make_job(cell.traffic, seed, j,
                                  shape["volumes_per_job"],
                                  shape["nominal_length"])
                for j in range(shape["pool_jobs"])]
    warm_up(cfg, shape)
    setup_s = time.perf_counter() - t_start

    summary = None
    if not trace:
        jobs, window_s = timed_jobs(cfg, pool, shape, seconds)
    else:
        tmp = tempfile.mkdtemp(prefix="bench_trace_")
        try:
            jobs, window_s = traced_jobs(cfg, pool, shape, seconds, tmp)
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
    checks = compare(cell.config, pool, jobs)
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
