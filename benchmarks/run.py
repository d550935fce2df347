"""Benchmark harness entry point — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--suite quick|mid|full]

Suites fix the whole geometry — the corpus registry scale/length every
figure driver sweeps (``benchmarks.corpus_figures``) AND the legacy
synthetic trace length fig8 still uses — so every ``BENCH_sweep.json``
is comparable against the matching per-geometry baseline
(``BENCH_baseline_<suite>.json``, ``benchmarks.compare``): ``quick`` is
CI-sized, ``mid`` the development default, ``full`` runs the
paper-scale 135-trace corpus. ``--quick`` stays as an alias for
``--suite quick``.

Prints ``name,seconds,derived`` CSV summary lines, writes detailed CSVs
to results/bench/, and emits ``results/bench/BENCH_sweep.json`` — the
machine-readable perf trajectory (per-config hit ratios, precision,
wall-clock, compile counts) that CI archives so
future PRs can compare against it. (The multi-pod dry-run + roofline
table have their own entry points: repro.launch.dryrun and
benchmarks.roofline_table — they need the 512-device XLA flag set
before jax import.)
"""

from __future__ import annotations

import argparse
import time
import traceback

SUITES = {
    # corpus registry scale + legacy synthetic length (fig8); the
    # per-scale corpus length is pinned once, in
    # benchmarks.corpus_figures.DEFAULT_LEN
    "quick": dict(trace_len=20_000, corpus_scale="quick"),
    "mid": dict(trace_len=40_000, corpus_scale="mid"),
    "full": dict(trace_len=40_000, corpus_scale="full"),
}


def _parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--suite", choices=sorted(SUITES), default=None,
                    help="benchmark geometry (default: mid)")
    ap.add_argument("--quick", action="store_true",
                    help="alias for --suite quick (CI-speed)")
    ap.add_argument("--corpus-dir", default=None,
                    help="run the corpus-backed jobs on an ingested "
                         "trace directory (traces.io.ingest_to_dir) "
                         "instead of the synthetic registry; "
                         "REPRO_CORPUS_DIR env var works too")
    return ap


def main(argv=None) -> None:
    ap = _parser()
    a = ap.parse_args(argv)
    if a.quick and a.suite not in (None, "quick"):
        ap.error(f"--quick contradicts --suite {a.suite}")
    suite = a.suite or ("quick" if a.quick else "mid")
    geo = SUITES[suite]

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    scale, tlen = geo["corpus_scale"], geo["trace_len"]

    from repro.traces import resolve_corpus_dir
    cdir = resolve_corpus_dir(a.corpus_dir)

    from . import (adaptive_bench, common, corpus_figures, corpus_sweep,
                   expert_prefetch, fig5_representative,
                   fig6_hrc_precision, fig7_params, fig8_latency,
                   fig9_midfreq, fig34_trace_sweep, kernel_micro,
                   serving_bench, table1_hit_ratio, tiered_serving)

    clen = corpus_figures.DEFAULT_LEN[scale]

    # the BENCH meta "corpus" geometry key: "synthetic", or the
    # ingested corpus' content fingerprint at this suite's slice —
    # compare.py treats a mismatch as a geometry change and skips
    # cross-population comparisons (a bad --corpus-dir fails fast here,
    # before any job burns compile time)
    corpus = "synthetic"
    if cdir:
        from repro.traces import RealCorpus
        corpus = RealCorpus(cdir).fingerprint(scale, clen)
        print(f"corpus: {cdir} (fingerprint {corpus})")

    jobs = [
        ("table1_hit_ratio",
         lambda: table1_hit_ratio.main(scale, clen, cdir)),
        ("fig34_trace_sweep",
         lambda: fig34_trace_sweep.main(scale, clen, cdir)),
        ("fig5_representative",
         lambda: fig5_representative.main(scale, clen, cdir)),
        ("fig6_hrc_precision",
         lambda: fig6_hrc_precision.main(scale, clen, cdir)),
        ("fig7_params", lambda: fig7_params.main(scale, clen, cdir)),
        ("fig8_latency", lambda: fig8_latency.main(tlen)),
        ("fig9_midfreq", lambda: fig9_midfreq.main(scale, clen, cdir)),
        ("corpus_sweep", lambda: corpus_sweep.main(scale, clen, cdir)),
        ("adaptive_bench", lambda: adaptive_bench.main(scale, clen, cdir)),
        ("tiered_serving", tiered_serving.main),
        ("serving_bench", lambda: serving_bench.main(scale, cdir)),
        ("expert_prefetch", expert_prefetch.main),
        ("kernel_micro", kernel_micro.main),
    ]

    print("name,seconds,derived")
    failures = 0
    job_log = []
    for name, fn in jobs:
        t0 = time.time()
        try:
            derived = fn()
            dt = time.time() - t0
            print(f"{name},{dt:.1f},{derived if derived else ''}")
            job_log.append({"job": name, "seconds": round(dt, 1),
                            "status": "ok"})
        except Exception as e:
            failures += 1
            dt = time.time() - t0
            traceback.print_exc()
            print(f"{name},{dt:.1f},FAILED:{type(e).__name__}")
            job_log.append({"job": name, "seconds": round(dt, 1),
                            "status": f"FAILED:{type(e).__name__}"})

    import jax
    device = jax.devices()[0]
    common.write_bench_json(
        meta={"suite": suite, "quick": suite == "quick",
              "trace_len": tlen,
              "corpus_scale": scale, "corpus_len": clen,
              "corpus": corpus,
              "jax": jax.__version__,
              "backend": jax.default_backend(),
              "platform": device.platform,
              "device_kind": device.device_kind,
              "n_devices": jax.local_device_count(),
              "failures": failures},
        jobs=job_log)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
