"""Corpus-native figure engine: one streaming sweep per config, shared.

Every figure driver (``table1_hit_ratio``, ``fig34_trace_sweep``,
``fig5_representative``, ``fig6_hrc_precision``, ``fig7_params``,
``fig9_midfreq``) and the corpus Table-1 job run through this engine
instead of private simulation passes: a :class:`CorpusRun` builds the
corpus registry slice once (traces, per-trace workload families,
degenerate flags) and memoizes one ``sweep_streaming`` result per
configuration — so the whole figure set costs ONE sweep (and one
compile) per distinct config, however many figures read it.

Two aggregation schemas come with it:

* **per-family breakdowns** — every figure emits a ``*_by_family.csv``
  sibling giving each workload family's (seq/loop/zipf/midfreq/mixed)
  mean next to the aggregate, the per-access-pattern-class reporting
  the prefetching literature asks of prefetcher claims;
* **degenerate surfacing** — traces with fewer than two requests carry
  ``degenerate=True`` columns instead of being silently dropped from
  summaries (`traces/io.py::workload_stats` reports totals for them;
  the CSVs now do too).

Scales follow the corpus registry: ``quick`` (16) ⊂ ``mid`` (64) ⊂
``full`` (135); capacity-/parameter-sensitivity figures (fig6/fig7) run
on the nested quick slice at every suite so their config grids stay
affordable, while the population figures (table1/fig34/fig5/fig9) use
the suite's full slice.

**Real-corpus drop-in (DESIGN.md §13):** every driver takes
``--corpus-dir`` (or the ``REPRO_CORPUS_DIR`` env var) naming an
ingested corpus directory (``traces.io.ingest_to_dir``); the engine
then builds its bundle from :class:`~repro.traces.RealCorpus` instead
of the synthetic registry — same engine, same CSV schemas — and
suffixes every BENCH job key with the corpus fingerprint so
``benchmarks.compare`` skips cleanly across different trace
populations.
"""

from __future__ import annotations

import argparse
from typing import Dict, List, Mapping, Optional

import numpy as np

from repro.cache import SimConfig, StreamResult, SweepResult, \
    sweep_streaming
from repro.traces import (FAMILIES, SCALES, RealCorpus, build_corpus,
                          corpus_specs, family_of, resolve_corpus_dir)
from repro.traces.synthetic import stack_padded

from .common import CAPACITY, configs, job_tag, record_sweep, write_csv

# nominal per-trace request counts per suite (same geometry the
# benchmark harness pins in run.py / compare.py baselines)
DEFAULT_LEN = {"quick": 4_000, "mid": 20_000, "full": 50_000}

ELIGIBLE_MIN_HR = 0.01      # LRU baseline below this -> relative gain
                            # is unbounded; report absolute delta only


class CorpusRun:
    """One corpus slice + the memoized streaming sweeps over it.

    ``result(cname)`` sweeps a registry config (``benchmarks.common
    .configs``) through ``sweep_streaming`` and memoizes by config, so
    every figure reading the same config shares one pass;
    ``extra_result(cfg, cname, job)`` does the same for figure-specific
    configs (fig6 capacities, fig7 parameter grid) — equal configs
    collapse onto the same sweep (``SimConfig`` is frozen/hashable).
    """

    def __init__(self, scale: str, trace_len: Optional[int] = None,
                 capacity: int = CAPACITY,
                 corpus_dir: Optional[str] = None):
        self.scale = scale
        self.trace_len = trace_len or DEFAULT_LEN[scale]
        self.capacity = capacity
        self.corpus_dir = resolve_corpus_dir(corpus_dir)
        (self.names, self.blocks, self.lengths, self.families,
         self.degenerate, self.fingerprint) = _corpus_bundle(
             scale, self.trace_len, self.corpus_dir)
        self.job = self.job_name(f"corpus_figures_{scale}")
        self._configs = configs(capacity)
        self._streams: Dict[SimConfig, StreamResult] = {}
        self._recorded: set = set()

    @property
    def corpus(self) -> str:
        """BENCH meta value: the fingerprint, or ``"synthetic"``."""
        return self.fingerprint or "synthetic"

    def job_name(self, base: str) -> str:
        """Job key for a driver sharing this run's corpus: tagged with
        the corpus fingerprint on ingested traces, bare on synthetic."""
        return job_tag(base, self.fingerprint)

    @property
    def n_traces(self) -> int:
        return len(self.names)

    def config(self, cname: str) -> SimConfig:
        return self._configs[cname]

    def _stream(self, cfg: SimConfig) -> StreamResult:
        if cfg not in self._streams:
            self._streams[cfg] = sweep_streaming(cfg, self.blocks,
                                                 self.lengths)
        return self._streams[cfg]

    def stream(self, cname: str) -> StreamResult:
        """A registry config's memoized sweep with its schedule
        telemetry (``StreamResult.streaming_stats``)."""
        return self._stream(self.config(cname))

    def result(self, cname: str) -> SweepResult:
        """Memoized sweep of a registry config, recorded once under the
        engine's shared job key (stable BENCH json keys regardless of
        which figure asks first — even when a figure-specific
        ``extra_result`` with an equal config swept it earlier)."""
        cfg = self.config(cname)
        res = self._stream(cfg).result
        if (self.job, cname) not in self._recorded:
            self._recorded.add((self.job, cname))
            record_sweep(self.job, cname, cfg, res)
        return res

    def results(self, cnames) -> Dict[str, SweepResult]:
        return {c: self.result(c) for c in cnames}

    def hit_ratios(self, cnames) -> Dict[str, np.ndarray]:
        return {c: self.result(c).hit_ratios() for c in cnames}

    def extra_result(self, cfg: SimConfig, cname: str,
                     job: str) -> SweepResult:
        """Sweep a figure-specific config; memoized by the config value,
        telemetry recorded once per (job, cname)."""
        res = self._stream(cfg).result
        if (job, cname) not in self._recorded:
            self._recorded.add((job, cname))
            record_sweep(job, cname, cfg, res)
        return res


_RUNS: Dict[tuple, CorpusRun] = {}
_BUNDLES: Dict[tuple, tuple] = {}


def _corpus_bundle(scale: str, trace_len: int,
                   corpus_dir: Optional[str] = None) -> tuple:
    """Traces/metadata per (scale, trace_len, corpus) —
    capacity-agnostic, so the fig6 capacity grid shares one corpus
    slice instead of rebuilding it per capacity.

    Synthetic (``corpus_dir=None``): generate the registry slice,
    fingerprint ``None``. Ingested: load the :class:`RealCorpus`,
    subset/cap it through the same nested-scale rule, families from
    the manifest, fingerprint of the sampled content.
    """
    key = (scale, trace_len, corpus_dir)
    if key not in _BUNDLES:
        if corpus_dir:
            rc = RealCorpus(corpus_dir)
            names, blocks, lengths = rc.suite(scale, trace_len)
            names = list(names)
            families = np.array([rc.family(n) for n in names])
            fingerprint = rc.fingerprint(scale, trace_len)
        else:
            specs = corpus_specs(trace_len, scale)
            names, blocks, lengths = stack_padded(build_corpus(specs))
            names = list(names)
            families = np.array([family_of(n) for n in names])
            fingerprint = None
        _BUNDLES[key] = (names, blocks, lengths, families,
                         np.asarray(lengths) <= 1, fingerprint)
    return _BUNDLES[key]


def corpus_run(scale: str, trace_len: Optional[int] = None,
               capacity: int = CAPACITY,
               corpus_dir: Optional[str] = None) -> CorpusRun:
    """Process-wide memoized :class:`CorpusRun` per corpus geometry."""
    corpus_dir = resolve_corpus_dir(corpus_dir)
    key = (scale, trace_len or DEFAULT_LEN[scale], capacity, corpus_dir)
    if key not in _RUNS:
        _RUNS[key] = CorpusRun(scale, trace_len, capacity, corpus_dir)
    return _RUNS[key]


def reset_engine() -> None:
    """Drop memoized corpus runs (test isolation)."""
    _RUNS.clear()
    _BUNDLES.clear()


# ---------------------------------------------------------------------------
# Aggregation schemas shared by the figure drivers
# ---------------------------------------------------------------------------

def family_rows(families, columns: Mapping[str, np.ndarray]) -> List[list]:
    """Per-family means of each column, plus an ``all`` aggregate row.

    Rows are ``[family, n, mean(col) ...]`` in registry family order
    (families with no traces at this scale are omitted), followed by
    any non-registry families present — ``ingested`` volumes and
    manifest-labeled real traces surface as their own rows instead of
    being dropped; NaN entries (e.g. precision of a config that never
    prefetched) are excluded from means and an all-NaN mean reports
    empty.
    """
    families = np.asarray(families)
    cols = {k: np.asarray(v, np.float64) for k, v in columns.items()}

    def mean(v):
        return ("" if np.isnan(v).all()
                else round(float(np.nanmean(v)), 6))

    extras = sorted(set(families.tolist()) - set(FAMILIES))
    rows = []
    for fam in list(FAMILIES) + extras:
        m = families == fam
        if m.any():
            rows.append([fam, int(m.sum())]
                        + [mean(v[m]) for v in cols.values()])
    rows.append(["all", len(families)] + [mean(v) for v in cols.values()])
    return rows


def write_family_csv(fname: str, families,
                     columns: Mapping[str, np.ndarray]) -> List[list]:
    """Write the per-family breakdown CSV; returns its rows."""
    rows = family_rows(families, columns)
    write_csv(fname, "family,n," + ",".join(columns), rows)
    return rows


def improvement_summary(hrs: Mapping[str, np.ndarray],
                        degenerate: np.ndarray,
                        base: str = "lru") -> List[list]:
    """Improvement-over-baseline rows, degenerates surfaced not dropped.

    Relative improvement is only meaningful where the baseline has a
    real hit ratio (the corpus deliberately contains reuse-free
    sequential workloads whose LRU hit ratio is ~0, where a ratio is
    unbounded); those traces — and degenerate len<=1 traces — still
    report through the absolute-delta column and the counts, instead of
    silently vanishing from the summary.
    """
    base_hr = np.asarray(hrs[base])
    eligible = (base_hr >= ELIGIBLE_MIN_HR) & ~degenerate
    rows = []
    for c in hrs:
        if c == base:
            continue
        delta = np.asarray(hrs[c]) - base_hr
        rel = delta[eligible] / base_hr[eligible]
        rows.append([c,
                     f"{rel.mean() * 100:.1f}%" if eligible.any() else "",
                     f"{rel.max() * 100:.1f}%" if eligible.any() else "",
                     int(eligible.sum()),
                     f"{delta.mean() * 100:.1f}pp",
                     int(degenerate.sum())])
    return rows


IMPROVEMENT_HEADER = ("algorithm,avg_improvement,max_improvement,"
                      "traces_with_lru_baseline,avg_abs_delta,"
                      "degenerate_traces")


def figure_parser(doc: Optional[str]) -> argparse.ArgumentParser:
    """The uniform figure-driver CLI: ``--scale``/``--trace-len``/
    ``--corpus-dir``.

    ``tests/test_results_doc.py`` parses every command documented in
    RESULTS.md through the owning driver's ``_parser()``, so drivers
    share this builder instead of hand-rolling flags.
    """
    ap = argparse.ArgumentParser(
        description=(doc or "").strip().splitlines()[0] if doc else None)
    ap.add_argument("--scale", choices=sorted(SCALES), default="quick",
                    help="corpus registry scale (quick=16, mid=64, "
                         "full=135 traces)")
    ap.add_argument("--trace-len", type=int, default=None,
                    help="nominal requests per trace (default per "
                         "scale; a length CAP on ingested traces)")
    ap.add_argument("--corpus-dir", default=None,
                    help="ingested corpus directory (traces.io"
                         ".ingest_to_dir) to run on instead of the "
                         "synthetic registry; REPRO_CORPUS_DIR env "
                         "var works too")
    return ap
