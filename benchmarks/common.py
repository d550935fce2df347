"""Shared benchmark scaffolding: trace suite, configs, sweep runs, telemetry.

Config names come from ``SimConfig.label()`` — the single source of truth
for CSV columns and ``BENCH_sweep.json`` keys — so adding a config here
can never drift from the name the sweep telemetry reports.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

import numpy as np

from repro.cache import SimConfig, SweepResult
from repro.cache.base import PF_AMP, PF_MITHRIL, PF_PG
from repro.configs.mithril_paper import SUITE_MITHRIL

RESULTS = os.path.join(os.path.dirname(__file__), "..", "results", "bench")

CAPACITY = 512          # blocks (the paper's 256MB at 4KB blocks, scaled to
                        # the synthetic LBA space so LRU spans 10-99% HR)


def configs(capacity: int = CAPACITY) -> Dict[str, SimConfig]:
    """The benchmark config grid, keyed by canonical ``label()``."""
    grid = [
        SimConfig(capacity=capacity),
        SimConfig(capacity=capacity, policy="fifo"),
        SimConfig(capacity=capacity, use_amp=True),
        SimConfig(capacity=capacity, use_pg=True),
        SimConfig(capacity=capacity, use_mithril=True,
                  mithril=SUITE_MITHRIL),
        SimConfig(capacity=capacity, policy="fifo", use_mithril=True,
                  mithril=SUITE_MITHRIL),
        SimConfig(capacity=capacity, use_amp=True, use_mithril=True,
                  mithril=SUITE_MITHRIL),
        SimConfig(capacity=capacity, use_learned=True),
        SimConfig(capacity=capacity, use_learned=True, use_mithril=True,
                  mithril=SUITE_MITHRIL),
    ]
    return {cfg.label(): cfg for cfg in grid}


def job_tag(job: str, corpus: Optional[str]) -> str:
    """BENCH job key for a corpus-parameterized job.

    Bare ``job`` on the synthetic registry; ``job@<fingerprint>`` on an
    ingested corpus (``traces.io.corpus_fingerprint``). Distinct keys
    per trace population mean ``benchmarks.compare`` reports real-corpus
    entries as new/unchecked instead of cross-comparing their hit ratios
    against synthetic baselines at the same job name.
    """
    return f"{job}@{corpus}" if corpus and corpus != "synthetic" else job


def pf_src_of(cfg: SimConfig) -> int:
    if cfg.use_mithril:
        return PF_MITHRIL
    if cfg.use_amp:
        return PF_AMP
    if cfg.use_pg:
        return PF_PG
    return 0


# --------------------------------------------------------------------------
# Sweep runs + telemetry for BENCH_sweep.json
# --------------------------------------------------------------------------

_TELEMETRY: List[dict] = []
_SERVING: List[dict] = []
_STREAMING: List[dict] = []
_KERNELS: List[dict] = []
_LEARNED: List[dict] = []


def record_sweep(job: str, config: str, cfg: SimConfig,
                 res: SweepResult) -> None:
    """Log one sweep for the machine-readable perf trajectory.

    Prints the canonical ``SimConfig.label()`` next to the result row —
    the same key BENCH_sweep.json and the README's config tables use —
    so job-local names (``delta=50``, ``mithril-lru@1024``) always
    resolve to a canonical configuration.
    """
    src = pf_src_of(cfg)
    prec = res.precisions(src) if src else np.full(res.n_traces, np.nan)
    entry = {
        "job": job,
        "config": config,
        "label": cfg.label(),
        "n_traces": int(res.n_traces),
        "hit_ratios": [round(float(h), 6) for h in res.hit_ratios()],
        "hit_ratio_mean": round(float(res.hit_ratios().mean()), 6),
        "precision_mean": (None if np.isnan(prec).all()
                           else round(float(np.nanmean(prec)), 6)),
        "seconds": round(float(res.seconds), 3),
        "compiles": int(res.compiles),
    }
    _TELEMETRY.append(entry)
    print(f"  [{job}] {config:<24} label={entry['label']:<18} "
          f"hit={entry['hit_ratio_mean']:.4f} "
          f"sec={entry['seconds']:7.2f} compiles={entry['compiles']}")


def sweep_telemetry() -> List[dict]:
    return list(_TELEMETRY)


def record_serving(job: str, config: str, metrics: Dict) -> None:
    """Log one measured serving run (``TieredServeEngine.metrics()``).

    The entry keeps the engine's split: virtual-step counters are
    deterministic and FAIL-gated by ``benchmarks.compare``; wall-clock
    throughput/latency only WARN.
    """
    entry = {"job": job, "config": config, **metrics}
    _SERVING.append(entry)
    print(f"  [{job}] {config:<16} tok={entry['tokens']} "
          f"occ={entry['mean_batch_occupancy']:.2f} "
          f"turn_p95={entry['turnaround_steps_p95']:.1f} "
          f"tier_hit={entry['tier']['hit_ratio']:.4f} "
          f"tok/s={entry['throughput_tok_s']:.1f} "
          f"step_p95={entry['step_latency_s_p95'] * 1e3:.2f}ms")


def serving_telemetry() -> List[dict]:
    return list(_SERVING)


def record_streaming(job: str, config: str, stats: Dict) -> None:
    """Log one streaming-engine run (``StreamResult.streaming_stats()``).

    The schedule counters (lane width, slab count, waste ratio, the
    async flag, plus any deterministic extras the caller folds in such
    as ``hit_ratio_mean``) are FAIL-gated by ``benchmarks.compare``;
    the ``"pipeline"`` timing/stall subdict — stage-busy seconds,
    producer/consumer stall counts, overlap efficiency — only WARNs.
    """
    entry = {"job": job, "config": config, **stats}
    _STREAMING.append(entry)
    p = entry.get("pipeline") or {}
    print(f"  [{job}] {config:<8} slabs={entry['n_slabs']} "
          f"waste={entry['waste_ratio']:.4f} "
          f"wall={p.get('wall_s', 0.0):.2f}s "
          f"overlap={p.get('overlap', 0.0):.2f} "
          f"stalls={p.get('producer_stalls', 0)}p/"
          f"{p.get('consumer_stalls', 0)}c")


def streaming_telemetry() -> List[dict]:
    return list(_STREAMING)


def record_kernel(kernel: str, shape: str, matches_oracle: bool,
                  roofline: Dict, wallclock_us: float = None) -> None:
    """Log one kernel-microbenchmark roofline point for BENCH json.

    ``roofline`` is ``KernelRoofline.to_dict()``: bytes moved and the
    arithmetic-intensity model are geometry-pure, so ``compare``
    FAIL-gates them (and ``matches_oracle``) like hit ratios; wall-clock
    is interpret-mode on CPU CI and only WARNs at the same geometry.
    """
    entry = {"kernel": kernel, "shape": shape,
             "matches_oracle": bool(matches_oracle),
             "wallclock_us": (None if wallclock_us is None
                              else round(float(wallclock_us), 1)),
             **roofline}
    _KERNELS.append(entry)
    print(f"  [kernel] {kernel:<22} {shape:<24} match={matches_oracle} "
          f"bytes={entry['bytes_moved'] / 1024:.0f}KB "
          f"ai={entry['intensity']:.3f} "
          f"peak_frac={entry['peak_fraction']:.4f}"
          + (f" wall={entry['wallclock_us']:.0f}us"
             if entry["wallclock_us"] is not None else ""))


def kernels_telemetry() -> List[dict]:
    return list(_KERNELS)


def record_learned(job: str, config: str, entry: Dict) -> None:
    """Log one adaptive-search run (``repro.learn.adapt``) for BENCH json.

    Everything except ``seconds`` is a pure function of (corpus, grid,
    seed) — committed arms, per-trace hit ratios, the decision-history
    CRC — so ``benchmarks.compare`` FAIL-gates those like hit ratios;
    wall-clock only WARNs.
    """
    entry = {"job": job, "config": config, **entry}
    _LEARNED.append(entry)
    print(f"  [{job}] {config:<12} hit={entry['hit_ratio_mean']:.4f} "
          f"static={entry['base_hit_ratio_mean']:.4f} "
          f"episodes={entry['episodes']} compiles={entry['compiles']} "
          f"crc={entry['decisions_crc']}")


def learned_telemetry() -> List[dict]:
    return list(_LEARNED)


def write_bench_json(meta: dict, jobs: List[dict]) -> str:
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, "BENCH_sweep.json")
    with open(path, "w") as f:
        json.dump({"meta": meta, "jobs": jobs,
                   "sweeps": sweep_telemetry(),
                   "serving": serving_telemetry(),
                   "streaming": streaming_telemetry(),
                   "kernels": kernels_telemetry(),
                   "learned": learned_telemetry()}, f, indent=2)
    print(f"wrote {path}")
    return path


def write_csv(fname: str, header: str, rows):
    os.makedirs(RESULTS, exist_ok=True)
    path = os.path.join(RESULTS, fname)
    with open(path, "w") as f:
        f.write(header + "\n")
        for r in rows:
            f.write(",".join(str(x) for x in r) + "\n")
    print(f"wrote {path}")
    return path


def timed(fn, *args):
    t0 = time.time()
    out = fn(*args)
    return out, time.time() - t0
