"""A configuration and a job shape small enough for the CPU: small
tables, so that mining fires many times in a few hundred requests."""

import json
from pathlib import Path

DRIVERS = Path(__file__).resolve().parents[1] / "drivers"

TINY_CONFIG = {
    "name": "tiny", "source": "test", "reduced": [], "assumed": {},
    "capacity": 32, "ways": 4, "policy": "lru",
    "use_mithril": True, "use_amp": True, "use_pg": False,
    "use_learned": False,
    "min_support": 2, "max_support": 3, "lookahead": 20, "prefetch_list": 2,
    "rec_buckets": 128, "rec_ways": 2, "mine_rows": 8, "pf_buckets": 4,
    "pf_ways": 2, "record_on": "miss", "max_window": 0, "max_pairs": 10,
    "symmetric": False,
    "n_streams": 8, "init_degree": 4, "max_degree": 8, "min_run": 2,
}
TINY_SHAPE = {"lanes": 4, "volumes_per_job": 6, "nominal_length": 384,
              "chunk": 128, "pool_jobs": 2}
TRAFFIC = {"volumes": [
    {"family": "midfreq", "length_frac": 1.0,
     "params": {"n_groups": 40, "group_size": 4, "reuse": 6, "spread": 3}},
    {"family": "mixed", "length_frac": 0.7,
     "params": {"w_seq": 0.3, "w_assoc": 0.4, "w_zipf": 0.3}},
    {"family": "loop", "length_frac": 0.45,
     "params": {"loop_len": 60, "n_loops": 2, "jitter": 0.01}},
    {"family": "seq", "length_frac": 0.85,
     "params": {"n_streams": 4, "run_len": 16, "skip_prob": 0.05}},
    {"family": "zipf", "length_frac": 0.6,
     "params": {"alpha": 1.2, "catalog": 4096}}]}


def write_root(root, metrics_src, extra_metrics=(), per_layer_extra=(),
               config=TINY_CONFIG):
    """A checkout-like tree under ``root`` with one tiny cell ``t-cell``:
    BENCHMARK.json plus the files it names. The metric readers are copied
    from ``metrics_src``, the drivers from the benchmark's own."""
    bench = root / "bench"
    for d in ("configs", "traffic", "cells", "metrics", "drivers"):
        (bench / d).mkdir(parents=True, exist_ok=True)
    (bench / "configs" / "tiny.json").write_text(json.dumps(config))
    for p in DRIVERS.glob("*.py"):
        (bench / "drivers" / p.name).write_text(p.read_text())
    (bench / "traffic" / "mix.json").write_text(json.dumps(TRAFFIC))
    (bench / "cells" / "t-cell.json").write_text(json.dumps(TINY_SHAPE))
    for p in metrics_src.glob("*.py"):
        (bench / "metrics" / p.name).write_text(p.read_text())
    for name, text in extra_metrics:
        (bench / "metrics" / f"{name}.py").write_text(text)
    spec = {
        "command": ["python3", "bench/run.py"], "paths": ["bench"],
        "run_seconds": 1,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": "t-cell", "config": "tiny", "traffic": "mix",
                       "chips": 1, "why": "test"}],
        "end_to_end": [
            {"name": "req_per_s", "unit": "req/s", "better": "higher",
             "bound": 0.05, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": [
            {"name": "lane_waste", "unit": "%", "better": "lower",
             "source": "program_counter", "layer": "streaming engine",
             "moves": "req_per_s"}, *per_layer_extra],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root
