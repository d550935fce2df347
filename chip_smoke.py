#!/usr/bin/env python3
"""Smoke run of the system's main paths on a TPU, with compiled kernels.

    python3 chip_smoke.py                # one chip: phases (a), (b), (c)
    python3 chip_smoke.py --four-chips   # four chips: the sharded sweep only

(a) ``mithril-lru`` (SUITE_MITHRIL tables, capacity 512) over the mid
    corpus (64 traces x 20,000 requests) through ``sweep_scheduled``.
    The per-trace hit ratios must equal the ``corpus_figures_mid``
    entry of ``results/bench/BENCH_baseline_mid.json`` exactly, and the
    chunk runner must carry the compiled record and mining kernels.
(b) ``mithril-lru`` with PAPER_MITHRIL tables over the full corpus
    (135 traces, the first ``DEPTH`` requests of each) through
    ``sweep_streaming``, one lane per trace: about 0.8 GB of MITHRIL
    state on the device. A slice of the traces rerun with the record
    and mining steps in their jnp forms must agree bit for bit.
(c) ``TieredServeEngine`` answers the ``serving_bench`` quick workload
    with ``paged_decode`` compiled. Its deterministic counters must
    equal the CPU-recorded ``serving_quick`` entries of
    ``results/bench/BENCH_baseline_quick.json``, and one decode must
    agree with the jnp reference computed on the host.
--four-chips: the full corpus (SUITE_MITHRIL, first ``DEPTH`` requests)
    through ``sweep_streaming`` on 136 lanes split over four chips,
    against the same sweep on one device: 4 shards, one compile,
    bit-identical statistics.

Depth is cut because the sweep costs about 60 us per lane and request
on a v5e today: the whole 135 x 50,000 corpus takes ~10 minutes.

Each phase prints one line: compile (set-up) seconds, steady seconds
and its counts. The script runs in one process, exits non-zero when a
phase fails or no TPU is found, and only then prints, as the last line
of standard output, ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import re
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

MID_BASELINE = ROOT / "results" / "bench" / "BENCH_baseline_mid.json"
QUICK_BASELINE = ROOT / "results" / "bench" / "BENCH_baseline_quick.json"
CHUNK = 4096
DEPTH = 3 * CHUNK        # requests per trace in phase (b) and --four-chips
REF_TRACES = 8           # traces of phase (b) rerun with the jnp forms
SERVING_COUNTERS = ("requests", "tokens", "steps", "mean_batch_occupancy",
                    "turnaround_steps_p50", "turnaround_steps_p95",
                    "turnaround_steps_p99", "tier")


def log(line: str) -> None:
    print(line, flush=True)


def compiled_kernels(lowered) -> list:
    """Names of the Pallas kernels a lowering runs compiled on the TPU
    (each is a ``tpu_custom_call``; an interpreted kernel is not)."""
    text = lowered.as_text()
    return sorted(set(re.findall(
        r'tpu_custom_call.*?kernel_name = "([^"]+)"', text)))


def kernels_in_runner(cfg, chunk: int, width: int) -> list:
    """Compiled kernels in the sweep's chunk runner at one
    ``(chunk, width)`` slab shape."""
    import jax
    import jax.numpy as jnp
    sweep_mod = importlib.import_module("repro.cache.sweep")

    init_batched, run_chunk, _ = sweep_mod._runner(cfg, 1, 1)
    carry = jax.eval_shape(lambda: init_batched(width))
    return compiled_kernels(run_chunk.lower(
        carry, jax.ShapeDtypeStruct((chunk, width), jnp.int32),
        jax.ShapeDtypeStruct((chunk, width), jnp.bool_)))


@contextlib.contextmanager
def jnp_forms():
    """Run the sweep engine with its record and mining steps in their
    pure-jnp forms (the CPU path) on the current device."""
    sweep_mod = importlib.import_module("repro.cache.sweep")

    saved = sweep_mod._batched_record_fn, sweep_mod._batched_pairwise_fn
    sweep_mod._batched_record_fn = lambda: None
    sweep_mod._batched_pairwise_fn = lambda: (None, None)
    sweep_mod.reset_runners()
    try:
        yield
    finally:
        sweep_mod._batched_record_fn, sweep_mod._batched_pairwise_fn = saved
        sweep_mod.reset_runners()


def stream(cfg, blocks, lengths, lanes: int, **kw):
    """Warm the ``(CHUNK, lanes)`` runner on a one-chunk prefix, then
    stream the whole corpus; returns (StreamResult, compile s, steady s)."""
    import numpy as np
    from repro.cache.sweep import sweep_streaming

    t0 = time.perf_counter()
    sweep_streaming(cfg, blocks[:lanes, :CHUNK],
                    np.minimum(lengths[:lanes], CHUNK),
                    lane_width=lanes, chunk=CHUNK, **kw)
    t1 = time.perf_counter()
    out = sweep_streaming(cfg, blocks, lengths, lane_width=lanes,
                          chunk=CHUNK, **kw)
    t2 = time.perf_counter()
    if out.result.compiles != 0:
        raise AssertionError(f"{out.result.compiles} compiles in the "
                             "timed sweep")
    return out, t1 - t0, t2 - t1


def full_corpus():
    """The 135-trace full corpus cut to its first ``DEPTH`` requests."""
    import numpy as np
    from repro.traces import corpus_suite

    _, blocks, lengths = corpus_suite("full", 50_000)
    return blocks[:, :DEPTH], np.minimum(lengths, DEPTH)


def assert_same(a, b, rows_a, rows_b, what: str) -> None:
    import numpy as np

    for f in a.stats._fields:
        if not np.array_equal(np.asarray(getattr(a.stats, f))[rows_a],
                              np.asarray(getattr(b.stats, f))[rows_b]):
            raise AssertionError(f"{what}: stats.{f} differ")
    if not np.array_equal(a.hit_curve[rows_a], b.hit_curve[rows_b]):
        raise AssertionError(f"{what}: hit curves differ")


def phase_mid() -> str:
    import numpy as np
    from repro.cache import SimConfig, plan_sweep, sweep_scheduled
    from repro.cache.sweep import sweep_streaming
    from repro.configs.mithril_paper import SUITE_MITHRIL
    from repro.traces import corpus_suite

    cfg = SimConfig(capacity=512, use_mithril=True, mithril=SUITE_MITHRIL)
    _, blocks, lengths = corpus_suite("mid", 20_000)
    plan = plan_sweep(lengths)
    kernels = kernels_in_runner(cfg, *plan.shapes[-1])
    if not {"mithril_record", "mithril_mine_batched"} <= set(kernels):
        raise AssertionError(f"chunk runner kernels: {kernels}")
    t0 = time.perf_counter()
    for chunk, width in plan.shapes:
        sweep_streaming(cfg, np.ones((width, chunk), np.int32),
                        lane_width=width, chunk=chunk)
    t1 = time.perf_counter()
    res = sweep_scheduled(cfg, blocks, lengths, plan=plan)
    t2 = time.perf_counter()
    if res.compiles != 0:
        raise AssertionError(f"{res.compiles} compiles in the timed sweep")
    want = next(s for s in json.loads(MID_BASELINE.read_text())["sweeps"]
                if s["job"] == "corpus_figures_mid"
                and s["config"] == cfg.label())["hit_ratios"]
    got = [round(float(h), 6) for h in res.hit_ratios()]
    bad = sum(g != w for g, w in zip(got, want)) + abs(len(got) - len(want))
    if bad:
        raise AssertionError(f"{bad} of {len(want)} hit ratios differ from "
                             "the mid baseline")
    requests = int(np.asarray(res.stats.requests).sum())
    return (f"traces={len(got)} requests={requests} "
            f"hits={int(np.asarray(res.stats.hits).sum())} "
            f"shapes={list(plan.shapes)} kernels={kernels} "
            f"compile_s={t1 - t0:.3f} steady_s={t2 - t1:.3f} "
            f"baseline_equal=True")


def phase_paper() -> str:
    import jax
    import numpy as np
    from repro.cache import SimConfig
    from repro.configs.mithril_paper import PAPER_MITHRIL
    from repro.core import init_state

    cfg = SimConfig(capacity=512, use_mithril=True, mithril=PAPER_MITHRIL)
    blocks, lengths = full_corpus()
    lanes = len(lengths)
    kernels = kernels_in_runner(cfg, CHUNK, lanes)
    if not {"mithril_record", "mithril_mine_batched"} <= set(kernels):
        raise AssertionError(f"chunk runner kernels: {kernels}")
    out, compile_s, steady_s = stream(cfg, blocks, lengths, lanes)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    rows = np.linspace(0, len(lengths) - 1, REF_TRACES).astype(int)
    with jnp_forms():
        ref, ref_compile_s, ref_s = stream(cfg, blocks[rows], lengths[rows],
                                           REF_TRACES)
    assert_same(out.result, ref.result, rows, slice(None),
                "kernel vs jnp forms")
    res = out.result
    lane_state = jax.eval_shape(lambda: init_state(PAPER_MITHRIL))
    state = sum(leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree.leaves(lane_state))
    return (f"traces={res.n_traces} "
            f"requests={int(np.asarray(res.stats.requests).sum())} "
            f"hits={int(np.asarray(res.stats.hits).sum())} "
            f"lanes={out.lane_width} slabs={out.n_slabs} "
            f"mithril_state_bytes={state * out.lane_width} "
            f"peak_bytes_in_use={peak} kernels={kernels} "
            f"compile_s={compile_s:.3f} steady_s={steady_s:.3f} "
            f"ref_traces={len(rows)} ref_compile_s={ref_compile_s:.3f} "
            f"ref_steady_s={ref_s:.3f} jnp_equal=True")


def phase_serving() -> str:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.serving_bench import PAGE, SCALES, serve
    from repro.kernels import ops, ref

    geo = SCALES["quick"]
    base = {e["config"]: e for e in
            json.loads(QUICK_BASELINE.read_text())["serving"]}
    # one decode at the tier's geometry, against the reference on the host
    rng = np.random.default_rng(0)
    b, hq, n_pg = geo["max_batch"], 4, geo["pages_per_req"]
    pool = (geo["n_hbm_slots"], PAGE["page_size"], PAGE["n_kv"],
            PAGE["head_dim"])
    args = (rng.standard_normal((b, hq, PAGE["head_dim"]), np.float32),
            rng.standard_normal(pool, np.float32),
            rng.standard_normal(pool, np.float32),
            rng.permutation(pool[0])[: b * n_pg].reshape(b, n_pg)
            .astype(np.int32),
            np.asarray([n_pg * PAGE["page_size"] - 3 * i for i in range(b)],
                       np.int32))
    kernels = compiled_kernels(ops.paged_decode.lower(*args))
    if kernels != ["paged_decode"]:
        raise AssertionError(f"paged_decode lowering kernels: {kernels}")
    got = np.asarray(ops.paged_decode(*args))
    with jax.default_device(jax.devices("cpu")[0]):
        want = np.asarray(ref.paged_decode_ref(*map(jnp.asarray, args)))
    err = float(np.max(np.abs(got - want)))
    if not np.allclose(got, want, rtol=2e-2, atol=2e-2):
        raise AssertionError(f"paged_decode max abs error {err}")
    parts = []
    t0 = time.perf_counter()
    for config, mithril in (("lru_tier", False), ("mithril_tier", True)):
        m = serve(geo, mithril)
        diff = [k for k in SERVING_COUNTERS if m[k] != base[config][k]]
        if diff:
            raise AssertionError(f"{config}: counters {diff} differ from "
                                 "the CPU baseline")
        parts.append(f"{config}: tokens={m['tokens']} steps={m['steps']} "
                     f"hit_ratio={m['tier']['hit_ratio']} "
                     f"wall_s={m['wall_seconds']}")
    return (f"{'; '.join(parts)}; decode_max_abs_err={err:.3g} "
            f"kernels={kernels} serve_s={time.perf_counter() - t0:.3f} "
            f"counters_equal=True")


def phase_four_chips() -> str:
    import numpy as np
    from repro.cache import SimConfig
    from repro.configs.mithril_paper import SUITE_MITHRIL

    cfg = SimConfig(capacity=512, use_mithril=True, mithril=SUITE_MITHRIL)
    blocks, lengths = full_corpus()
    lanes = -(-len(lengths) // 4) * 4
    sharded, compile_s, steady_s = stream(cfg, blocks, lengths, lanes,
                                          shard=True)
    single, one_compile_s, one_s = stream(cfg, blocks, lengths, lanes,
                                          shard=False)
    if sharded.n_shards != 4 or single.n_shards != 1:
        raise AssertionError(f"shards: {sharded.n_shards}, "
                             f"{single.n_shards}")
    from repro.cache.sweep import compile_count
    compiles = compile_count(cfg, 1, 4)
    if compiles != 1:
        raise AssertionError(f"{compiles} sharded executables")
    rows = slice(None)
    assert_same(sharded.result, single.result, rows, rows,
                "4 shards vs one device")
    res = sharded.result
    return (f"traces={res.n_traces} "
            f"requests={int(np.asarray(res.stats.requests).sum())} "
            f"hits={int(np.asarray(res.stats.hits).sum())} "
            f"lanes={sharded.lane_width} n_shards={sharded.n_shards} "
            f"sharded_executables={compiles} compile_s={compile_s:.3f} "
            f"steady_s={steady_s:.3f} single_compile_s={one_compile_s:.3f} "
            f"single_steady_s={one_s:.3f} bit_identical=True")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the lane-sharded sweep on four chips "
                         "and the one-device sweep it is compared with")
    a = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {devices[0].platform})",
              file=sys.stderr)
        return 2
    want = 4 if a.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    log(f"device: {devices[0].device_kind} x{len(devices)} "
        f"jax {jax.__version__} compile_cache={cache}")

    phases = ([("four_chips", phase_four_chips)] if a.four_chips else
              [("a_mid", phase_mid), ("b_paper", phase_paper),
               ("c_serving", phase_serving)])
    failed = 0
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            log(f"[{name}] ok {fn()} phase_s={time.perf_counter() - t0:.3f}")
        except Exception as e:      # report every phase, then fail
            failed += 1
            log(f"[{name}] FAILED {type(e).__name__}: {e}")
            import traceback
            traceback.print_exc()
    if failed:
        return 1
    log(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
