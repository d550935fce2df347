"""Compare a fresh BENCH_sweep.json against its per-geometry baseline.

    PYTHONPATH=src python -m benchmarks.compare [--fresh PATH] [--baseline PATH]

Baselines are PER GEOMETRY (ISSUE 4 / ROADMAP): each benchmark suite —
``quick``, ``mid``, ``full`` (``benchmarks.run --suite``) — gates
against its own ``BENCH_baseline_<suite>.json``, resolved from the
fresh run's ``meta.suite``, so quick CI runs, development mid runs and
paper-scale corpus runs each keep an independent trajectory. A legacy
un-suffixed ``BENCH_baseline.json`` is used as fallback when the
per-geometry file does not exist yet.

Policy (make CI *compare* trajectories, not just archive them):

* hit-ratio drift on any (job, config) sweep present in both files is a
  FAILURE (exit 1): the simulator is integer arithmetic end to end, so
  hit ratios are bit-stable across machines — any drift is a semantics
  change and must be an intentional, baseline-updating commit;
* wall-clock regression beyond ``--wallclock-warn`` (default 20%) is a
  WARNING only — CI machines are noisy;
* sweeps missing from the baseline are reported and skipped (new
  benchmarks seed their own trajectory on the next baseline refresh);
  sweeps missing from the fresh run FAIL (a benchmark silently died);
* measured serving (ISSUE 6): the ``TieredServeEngine`` metrics split
  two ways — virtual-step counters (tokens, turnaround percentiles,
  batch occupancy, the whole tier counter dict) are deterministic
  given the workload, so any drift FAILS; wall-clock throughput and
  step-latency percentiles only WARN, like sweep wall-clock;
* streaming pipeline (ISSUE 9): the async-producer runs recorded in
  the ``"streaming"`` section split the same way — lane geometry, slab
  counts, waste ratio, the async flag and the folded-in mean hit ratio
  are deterministic and FAIL on drift (and an entry with no
  ``"pipeline"`` telemetry FAILS outright); stage-busy timings, ring
  stall counters and overlap efficiency are scheduling noise and only
  WARN;
* per-kernel roofline (ISSUE 7): kernel-vs-oracle agreement FAILs on
  mismatch, and the roofline bytes-moved model is pure arithmetic over
  the launch geometry, so any bytes regression vs the baseline FAILS
  (improvements are noted); interpret-mode kernel wall-clock only
  WARNs past ``--wallclock-warn`` at the same geometry;
* learned & adaptive lane (ISSUE 8): an adaptive-search run is a pure
  function of (corpus, grid, seed) — committed arms, per-trace hit
  ratios and the decision-history CRC all FAIL on drift; wall-clock
  only WARNs;
* schema skew is never a crash: a baseline that predates a whole
  section (e.g. ``BENCH_baseline_mid`` without ``"learned"``) or an
  entry field WARNs and skips that comparison — the next baseline
  refresh starts gating it.

Refresh a geometry's baseline by copying a trusted run of that suite:

    cp results/bench/BENCH_sweep.json results/bench/BENCH_baseline_quick.json
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(__file__)
BENCH_DIR = os.path.join(HERE, "..", "results", "bench")
HIT_TOL = 1e-9


def _key(sweep: dict) -> tuple:
    return (sweep["job"], sweep["config"])


def _index(doc: dict) -> dict:
    return {_key(s): s for s in doc.get("sweeps", [])}


def _baseline_section(baseline: dict, fresh: dict, name: str,
                      warnings: list) -> list:
    """A baseline telemetry section, tolerating older schemas.

    When the baseline predates the section entirely (e.g. a
    ``BENCH_baseline_mid`` seeded before the ``"learned"`` section
    existed) the fresh entries can't be gated — WARN once and skip
    rather than KeyError, so adding a section never breaks CI against
    old baselines; the next baseline refresh starts gating it.
    """
    if name in baseline:
        return baseline.get(name) or []
    if fresh.get(name):
        warnings.append(
            f"baseline has no '{name}' section (older schema) — "
            f"{len(fresh[name])} fresh entrie(s) unchecked; refresh "
            "the baseline to start gating them")
    return []


def compare(fresh: dict, baseline: dict, wallclock_warn: float):
    """Returns (failures, warnings, notes, n_compared)."""
    failures, warnings, notes = [], [], []
    fresh_ix, base_ix = _index(fresh), _index(baseline)

    fresh_meta, base_meta = fresh.get("meta", {}), baseline.get("meta", {})
    # keys present in BOTH metas must agree; n_traces (legacy synthetic
    # suite width) was dropped from fresh metas in ISSUE 5 — old
    # baselines that still carry it are compared on the live keys only.
    # "corpus" (the ingested-corpus fingerprint, ISSUE 10) defaults to
    # "synthetic" on BOTH sides so a real-corpus run vs a pre-ISSUE-10
    # baseline still registers as a geometry change and skips cleanly.
    fresh_meta = dict(fresh_meta,
                      corpus=fresh_meta.get("corpus", "synthetic"))
    base_meta = dict(base_meta,
                     corpus=base_meta.get("corpus", "synthetic"))
    geometry = ("quick", "trace_len", "corpus_scale", "corpus_len",
                "corpus")
    if any(k in fresh_meta and k in base_meta
           and fresh_meta[k] != base_meta[k] for k in geometry):
        notes.append(
            f"geometry differs (fresh={[fresh_meta.get(k) for k in geometry]}"
            f" baseline={[base_meta.get(k) for k in geometry]}): "
            "hit ratios are not comparable, only checking job health")
        base_ix = {}

    for key, base in base_ix.items():
        got = fresh_ix.get(key)
        if got is None:
            failures.append(f"{key}: sweep missing from fresh run")
            continue
        if len(got["hit_ratios"]) != len(base["hit_ratios"]):
            failures.append(
                f"{key}: trace count changed "
                f"{len(base['hit_ratios'])} -> {len(got['hit_ratios'])}")
            continue
        drift = [(i, b, g) for i, (b, g) in
                 enumerate(zip(base["hit_ratios"], got["hit_ratios"]))
                 if abs(b - g) > HIT_TOL]
        if drift:
            i, b, g = drift[0]
            failures.append(
                f"{key}: hit-ratio drift on {len(drift)} trace(s), e.g. "
                f"trace {i}: baseline={b:.6f} fresh={g:.6f}")
        if base.get("compiles") is None:
            warnings.append(f"{key}: baseline entry has no 'compiles' "
                            "(older schema) — compile count unchecked")
        elif got["compiles"] > max(base["compiles"], 1):
            failures.append(
                f"{key}: compile count regressed "
                f"{base['compiles']} -> {got['compiles']}")
        if base["seconds"] > 0 and (got["seconds"]
                                    > base["seconds"] * (1 + wallclock_warn)):
            warnings.append(
                f"{key}: wall-clock {base['seconds']:.2f}s -> "
                f"{got['seconds']:.2f}s "
                f"(+{100 * (got['seconds'] / base['seconds'] - 1):.0f}%)")

    for key in fresh_ix.keys() - base_ix.keys():
        notes.append(f"{key}: not in baseline (new sweep, unchecked)")

    # measured serving: deterministic counters FAIL, wall-clock WARNs
    det_keys = ("requests", "tokens", "steps", "mean_batch_occupancy",
                "turnaround_steps_p50", "turnaround_steps_p95",
                "turnaround_steps_p99", "tier")
    base_sv = {(s["job"], s["config"]): s
               for s in _baseline_section(baseline, fresh, "serving",
                                          warnings)}
    for s in fresh.get("serving", []):
        key = (s["job"], s["config"])
        b = base_sv.get(key)
        if b is None:
            notes.append(f"serving {key}: not in baseline "
                         "(new scenario, unchecked)")
            continue
        if not base_ix:     # geometry mismatch cleared the comparison
            continue
        for k in det_keys:
            if s.get(k) != b.get(k):
                failures.append(
                    f"serving {key}: deterministic counter '{k}' drifted "
                    f"{b.get(k)} -> {s.get(k)}")
        if b.get("throughput_tok_s", 0) > 0 and (
                s.get("throughput_tok_s", 0)
                < b["throughput_tok_s"] * (1 - wallclock_warn)):
            warnings.append(
                f"serving {key}: throughput {b['throughput_tok_s']:.1f} -> "
                f"{s['throughput_tok_s']:.1f} tok/s "
                f"(-{100 * (1 - s['throughput_tok_s'] / b['throughput_tok_s']):.0f}%)")

    for key in base_sv.keys() - {(s["job"], s["config"])
                                 for s in fresh.get("serving", [])}:
        if base_ix:
            failures.append(f"serving {key}: missing from fresh run")

    # streaming pipeline (ISSUE 9): schedule counters and the async
    # flag are deterministic given (corpus, lane geometry) — drift
    # FAILS, as does an entry missing its pipeline telemetry; stage
    # timings, stall counts and overlap are scheduling noise and WARN
    det_st = ("lane_width", "chunk", "n_slabs", "lane_steps",
              "ideal_lane_steps", "waste_ratio", "async_producer",
              "hit_ratio_mean")
    base_st = {(s["job"], s["config"]): s
               for s in _baseline_section(baseline, fresh, "streaming",
                                          warnings)}
    for s in fresh.get("streaming", []):
        key = (s["job"], s["config"])
        if not s.get("pipeline"):
            failures.append(f"streaming {key}: pipeline telemetry missing")
        b = base_st.get(key)
        if b is None:
            notes.append(f"streaming {key}: not in baseline "
                         "(new run, unchecked)")
            continue
        if not base_ix:     # geometry mismatch cleared the comparison
            continue
        for k in det_st:
            if k not in b:
                warnings.append(
                    f"streaming {key}: baseline entry predates '{k}' "
                    "(older schema) — unchecked")
            elif s.get(k) != b[k]:
                failures.append(
                    f"streaming {key}: deterministic counter '{k}' "
                    f"drifted {b[k]} -> {s.get(k)}")
        bp, sp = b.get("pipeline") or {}, s.get("pipeline") or {}
        if bp.get("wall_s", 0) > 0 and (
                sp.get("wall_s", 0) > bp["wall_s"] * (1 + wallclock_warn)):
            warnings.append(
                f"streaming {key}: wall-clock {bp['wall_s']:.2f}s -> "
                f"{sp['wall_s']:.2f}s "
                f"(+{100 * (sp['wall_s'] / bp['wall_s'] - 1):.0f}%)")
        if "overlap" in bp and "overlap" in sp \
                and sp["overlap"] < bp["overlap"] - 0.25:
            warnings.append(
                f"streaming {key}: overlap efficiency "
                f"{bp['overlap']:.2f} -> {sp['overlap']:.2f}")

    for key in base_st.keys() - {(s["job"], s["config"])
                                 for s in fresh.get("streaming", [])}:
        if base_ix:
            failures.append(f"streaming {key}: missing from fresh run")

    # per-kernel roofline (ISSUE 7): oracle agreement and the
    # geometry-pure cost model (bytes moved) FAIL on regression —
    # bytes are pure arithmetic over the launch geometry, so any
    # increase is a layout/blocking change that must be intentional;
    # interpret-mode wall-clock only WARNs, like sweep wall-clock
    base_kn = {(k["kernel"], k["shape"]): k
               for k in _baseline_section(baseline, fresh, "kernels",
                                          warnings)}
    for k in fresh.get("kernels", []):
        key = (k["kernel"], k["shape"])
        if not k.get("matches_oracle", True):
            failures.append(f"kernel {key}: kernel-vs-oracle mismatch")
        b = base_kn.get(key)
        if b is None:
            notes.append(f"kernel {key}: not in baseline "
                         "(new kernel point, unchecked)")
            continue
        if not base_ix:     # geometry mismatch cleared the comparison
            continue
        if b.get("bytes_moved") is None:
            warnings.append(f"kernel {key}: baseline entry has no "
                            "'bytes_moved' (older schema) — unchecked")
        elif k["bytes_moved"] > b["bytes_moved"] + HIT_TOL:
            failures.append(
                f"kernel {key}: bytes moved regressed "
                f"{b['bytes_moved']:.0f} -> {k['bytes_moved']:.0f}")
        elif k["bytes_moved"] < b["bytes_moved"] - HIT_TOL:
            notes.append(
                f"kernel {key}: bytes moved improved "
                f"{b['bytes_moved']:.0f} -> {k['bytes_moved']:.0f} "
                "(baseline refresh will pin it)")
        if (b.get("wallclock_us") and k.get("wallclock_us")
                and k["wallclock_us"]
                > b["wallclock_us"] * (1 + wallclock_warn)):
            warnings.append(
                f"kernel {key}: wall-clock {b['wallclock_us']:.0f}us -> "
                f"{k['wallclock_us']:.0f}us "
                f"(+{100 * (k['wallclock_us'] / b['wallclock_us'] - 1):.0f}%)")

    for key in base_kn.keys() - {(k["kernel"], k["shape"])
                                 for k in fresh.get("kernels", [])}:
        if base_ix:
            failures.append(f"kernel {key}: missing from fresh run")

    # learned & adaptive lane (ISSUE 8): an adaptive run's committed
    # arms, per-trace hit ratios and decision-history CRC are a pure
    # function of (corpus, grid, seed) — drift FAILS like hit ratios;
    # only wall-clock ('seconds') WARNs
    det_ln = ("episodes", "arms", "labels", "hit_ratios",
              "base_hit_ratios", "decisions_crc")
    base_ln = {(s["job"], s["config"]): s
               for s in _baseline_section(baseline, fresh, "learned",
                                          warnings)}
    for s in fresh.get("learned", []):
        key = (s["job"], s["config"])
        b = base_ln.get(key)
        if b is None:
            if base_ln:
                notes.append(f"learned {key}: not in baseline "
                             "(new adaptive run, unchecked)")
            continue
        if not base_ix:     # geometry mismatch cleared the comparison
            continue
        for k in det_ln:
            if k not in b:
                warnings.append(f"learned {key}: baseline entry has no "
                                f"'{k}' (older schema) — unchecked")
            elif s.get(k) != b[k]:
                failures.append(
                    f"learned {key}: deterministic field '{k}' drifted "
                    f"{b[k]} -> {s.get(k)}")
        if b.get("seconds", 0) > 0 and (
                s.get("seconds", 0)
                > b["seconds"] * (1 + wallclock_warn)):
            warnings.append(
                f"learned {key}: wall-clock {b['seconds']:.2f}s -> "
                f"{s['seconds']:.2f}s "
                f"(+{100 * (s['seconds'] / b['seconds'] - 1):.0f}%)")

    for key in base_ln.keys() - {(s["job"], s["config"])
                                 for s in fresh.get("learned", [])}:
        if base_ix:
            failures.append(f"learned {key}: missing from fresh run")

    failed_jobs = [j for j in fresh.get("jobs", [])
                   if j.get("status") != "ok"]
    for j in failed_jobs:
        failures.append(f"job {j.get('job')}: {j.get('status')}")
    return failures, warnings, notes, len(base_ix)


def baseline_path(fresh_meta: dict) -> str:
    """Per-geometry baseline for the fresh run's suite label, falling
    back to the legacy un-suffixed file when none exists yet."""
    suite = fresh_meta.get("suite")
    if suite:
        per_geo = os.path.join(BENCH_DIR, f"BENCH_baseline_{suite}.json")
        if os.path.exists(per_geo):
            return per_geo
    return os.path.join(BENCH_DIR, "BENCH_baseline.json")


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--fresh",
                    default=os.path.join(BENCH_DIR, "BENCH_sweep.json"))
    ap.add_argument("--baseline", default=None,
                    help="baseline json (default: BENCH_baseline_<suite>"
                         ".json for the fresh run's suite)")
    ap.add_argument("--wallclock-warn", type=float, default=0.20,
                    help="warn when wall-clock regresses past this fraction")
    return ap


def main(argv=None) -> int:
    a = _parser().parse_args(argv)

    with open(a.fresh) as f:
        fresh = json.load(f)
    if a.baseline is None:
        a.baseline = baseline_path(fresh.get("meta", {}))
    if not os.path.exists(a.baseline):
        print(f"no baseline at {a.baseline}; nothing to compare "
              "(check one in to start the trajectory)")
        return 0
    print(f"baseline: {a.baseline}")
    with open(a.baseline) as f:
        baseline = json.load(f)

    failures, warnings, notes, n = compare(fresh, baseline,
                                           a.wallclock_warn)
    for m in notes:
        print(f"NOTE  {m}")
    for m in warnings:
        print(f"WARN  {m}")
    for m in failures:
        print(f"FAIL  {m}")
    print(f"compared {n} baseline sweep(s): "
          f"{len(failures)} failure(s), {len(warnings)} warning(s)")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
