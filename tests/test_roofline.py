"""Per-kernel roofline analyzer + the BENCH ``"kernels"`` compare gate.

ISSUE 7 satellites: ``repro.roofline.analysis.analyze_kernel`` returns
finite positive cost models for every registered kernel, machine peaks
fall back sanely (finite, untrusted) on unknown backends, and a BENCH
json ``"kernels"`` section round-trips through ``benchmarks.compare``
with the documented gating (oracle mismatch / bytes regression /
missing point FAIL, wall-clock drift WARNs, improvements are notes).
"""

import math

import jax
import pytest

from repro.roofline import (HBM_BW, PEAK_FLOPS, KERNEL_MODELS,
                            analyze_kernel, machine_peaks)

GEOMS = {
    "mithril_record_fused": dict(lanes=4, n_buckets=16, ways=2, r_sup=2,
                                 mine_rows=16, s_sup=4),
    "mithril_mine": dict(mine_rows=256, s_sup=8, window=32),
    "hash_lookup": dict(queries=256, n_buckets=128, ways=4, plist=3),
    "paged_decode": dict(batch=4, heads_q=32, heads_kv=8, head_dim=128,
                         page_size=16, n_pages=8),
}


def test_every_registered_kernel_has_a_test_geometry():
    assert set(GEOMS) == set(KERNEL_MODELS)


@pytest.mark.parametrize("name", sorted(KERNEL_MODELS))
def test_analyzer_finite_positive(name):
    rl = analyze_kernel(name, GEOMS[name], backend="cpu")
    assert rl.kernel == name and rl.geometry == GEOMS[name]
    assert rl.bytes_moved > 0 and rl.flops > 0
    assert math.isfinite(rl.intensity) and rl.intensity > 0
    assert 0 < rl.peak_fraction <= 1
    d = rl.to_dict()
    for k in ("bytes_moved", "flops", "intensity", "peak_fraction",
              "trusted_peaks", "backend"):
        assert k in d


def test_analyzer_cost_scales_with_geometry():
    g = dict(GEOMS["mithril_record_fused"])
    small = analyze_kernel("mithril_record_fused", g, backend="cpu")
    g2 = dict(g, lanes=2 * g["lanes"])
    big = analyze_kernel("mithril_record_fused", g2, backend="cpu")
    assert big.bytes_moved == 2 * small.bytes_moved
    assert big.flops == 2 * small.flops
    assert big.intensity == pytest.approx(small.intensity)


def test_machine_peaks_trusted_only_on_tpu():
    tpu = machine_peaks("tpu", "TPU v5 lite")
    assert tpu.trusted and tpu.flops_per_s == PEAK_FLOPS \
        and tpu.bytes_per_s == HBM_BW
    with pytest.raises(ValueError, match="no published peaks"):
        machine_peaks("tpu", "TPU v99")
    for backend in ("cpu", "gpu", "warp9"):
        pk = machine_peaks(backend)
        assert not pk.trusted
        assert math.isfinite(pk.flops_per_s) and pk.flops_per_s > 0
        assert math.isfinite(pk.bytes_per_s) and pk.bytes_per_s > 0
    live = machine_peaks()
    assert live.backend == jax.default_backend()


# ---------------------------------------------------------------------------
# BENCH "kernels" section through benchmarks.compare
# ---------------------------------------------------------------------------

def _kernel_entry(**kw):
    base = {"kernel": "mithril_record_fused", "shape": "l=4,nb=16",
            "matches_oracle": True, "wallclock_us": 100.0,
            "bytes_moved": 40960.0, "flops": 1200.0}
    base.update(kw)
    return base


def _doc(kernels, meta=None):
    meta = dict({"suite": "quick", "quick": True, "trace_len": 100,
                 "corpus_scale": "quick", "corpus_len": 50,
                 "n_devices": 1}, **(meta or {}))
    # one shared sweep so base_ix is non-empty (geometry comparable)
    sweep = {"job": "j", "config": "c", "hit_ratios": [0.5],
             "seconds": 1.0, "compiles": 1}
    return {"meta": meta, "jobs": [], "sweeps": [sweep],
            "kernels": kernels}


def _compare(fresh, baseline, warn=0.20):
    from benchmarks.compare import compare
    return compare(fresh, baseline, warn)


def test_kernels_identical_passes():
    doc = _doc([_kernel_entry()])
    failures, warnings, notes, _ = _compare(doc, _doc([_kernel_entry()]))
    assert not failures and not warnings


def test_kernels_oracle_mismatch_fails():
    fresh = _doc([_kernel_entry(matches_oracle=False)])
    failures, _, _, _ = _compare(fresh, _doc([_kernel_entry()]))
    assert any("oracle" in f for f in failures)


def test_kernels_bytes_regression_fails_improvement_notes():
    failures, _, _, _ = _compare(
        _doc([_kernel_entry(bytes_moved=50000.0)]),
        _doc([_kernel_entry()]))
    assert any("bytes moved regressed" in f for f in failures)
    failures, _, notes, _ = _compare(
        _doc([_kernel_entry(bytes_moved=30000.0)]),
        _doc([_kernel_entry()]))
    assert not failures
    assert any("bytes moved improved" in n for n in notes)


def test_kernels_wallclock_drift_warns_only():
    failures, warnings, _, _ = _compare(
        _doc([_kernel_entry(wallclock_us=200.0)]),
        _doc([_kernel_entry(wallclock_us=100.0)]))
    assert not failures
    assert any("wall-clock" in w for w in warnings)


def test_kernels_missing_from_fresh_fails_new_point_notes():
    failures, _, _, _ = _compare(_doc([]), _doc([_kernel_entry()]))
    assert any("missing from fresh" in f for f in failures)
    failures, _, notes, _ = _compare(_doc([_kernel_entry()]), _doc([]))
    assert not failures
    assert any("not in baseline" in n for n in notes)


def test_kernels_geometry_mismatch_skips_value_gates():
    fresh = _doc([_kernel_entry(bytes_moved=50000.0)],
                 meta={"trace_len": 999})
    failures, warnings, notes, _ = _compare(fresh, _doc([_kernel_entry()]))
    assert not any("bytes" in f for f in failures)
    assert any("geometry differs" in n for n in notes)
