"""Paper-scale corpus sweep: Table 1's averages at 135-trace scale.

The paper's headline numbers (55% avg hit-ratio gain over LRU, 36% over
AMP) are averages over 135 block-storage traces. This job sweeps the
corpus registry through the figure engine (``benchmarks.corpus_figures``
-> ``cache.sweep.sweep_streaming``): traces stream through a pool of
recycled lanes, the lane axis shards over local devices, and the whole
corpus costs one compile per config — shared with every figure driver
reading the same configs. Emits the per-trace CSV (family + degenerate
flags — a len<=1 trace is surfaced, never silently dropped), the
improvement summary and the per-family breakdown, and prints the
schedule's lane waste.

    PYTHONPATH=src python -m benchmarks.corpus_sweep --scale quick

Scales: quick (16 traces, CI-sized), mid (64), full (135 — the paper's
corpus size).
"""

from __future__ import annotations

from .common import write_csv
from .corpus_figures import (IMPROVEMENT_HEADER, corpus_run, figure_parser,
                             improvement_summary, write_family_csv)

NAMES = ["lru", "mithril-lru", "pg-lru", "mithril-amp-lru"]


def main(scale: str = "quick", trace_len: int | None = None,
         corpus_dir: str | None = None) -> str:
    run = corpus_run(scale, trace_len, corpus_dir=corpus_dir)
    job = run.job_name(f"corpus_{scale}")
    n_degenerate = int(run.degenerate.sum())
    print(f"  [{job}] {run.n_traces} traces (len {run.lengths.min()}..."
          f"{run.lengths.max()})")
    if n_degenerate:
        print(f"  [{job}] {n_degenerate} degenerate trace(s) (len<=1) "
              "surfaced via the degenerate column, not dropped")

    results = run.results(NAMES)
    hrs = {c: results[c].hit_ratios() for c in NAMES}
    rows = [[run.names[i], run.families[i], int(run.lengths[i]),
             bool(run.degenerate[i])]
            + [round(float(hrs[c][i]), 6) for c in NAMES]
            for i in range(run.n_traces)]
    write_csv(f"corpus_{scale}.csv",
              "trace,family,requests,degenerate," + ",".join(NAMES), rows)

    write_csv(f"corpus_{scale}_summary.csv", IMPROVEMENT_HEADER,
              improvement_summary(hrs, run.degenerate))
    write_family_csv(f"corpus_{scale}_by_family.csv", run.families, hrs)

    # the schedule depends only on the lengths, so any config's will do
    st = run.stream(NAMES[0]).streaming_stats()
    print(f"  [{job}] {st['lane_width']} lanes x chunk {st['chunk']}, "
          f"{st['n_slabs']} slabs, lane waste {st['waste_ratio']}")

    worst = max(max(results[c].compiles, 0) for c in NAMES)
    return (f"traces={run.n_traces};max_compiles={worst};"
            f"degenerate={n_degenerate};"
            f"lane_waste={st['waste_ratio']}")


def _parser():
    return figure_parser(__doc__)


if __name__ == "__main__":
    a = _parser().parse_args()
    print(main(a.scale, a.trace_len, a.corpus_dir))
