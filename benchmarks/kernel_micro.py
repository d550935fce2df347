"""Kernel microbenchmarks: oracle-vs-kernel agreement + per-kernel roofline.

    PYTHONPATH=src python -m benchmarks.kernel_micro

Every Pallas kernel on the request path is checked bit-for-bit (exact
for the int32 mining/record kernels, tolerance for the float decode
kernel) against its jnp oracle, then priced by the per-kernel roofline
analyzer (``repro.roofline.analysis.analyze_kernel``): bytes moved
through VMEM, flops, arithmetic intensity and attainable machine-peak
fraction for the launch geometry. The roofline numbers are geometry-pure
(no timing involved) so ``benchmarks.compare`` FAIL-gates them like hit
ratios. Kernels run compiled on TPU and interpreted elsewhere
(``interpret`` resolves from the backend); an interpreter wall-clock is
not a device time (DESIGN.md §11), and wall-clock only ever WARNs.

Artifacts: ``kernel_micro.csv`` (agreement sweep), ``kernel_roofline.csv``
(the roofline table), plus the ``"kernels"`` section of
``BENCH_sweep.json`` when run under ``benchmarks.run``.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import MithrilConfig, init_state, record_event
from repro.core.mining import pairwise_codes
from repro.kernels import ops
from repro.roofline import analyze_kernel

from .common import record_kernel, write_csv

ROOFLINE_HEADER = ("kernel,shape,backend,bytes_moved,flops,intensity,"
                   "peak_fraction,trusted_peaks")


def mine_accounting(n, s, window, blk=128):
    vmem = n * s * 4 + 2 * n * 4 + blk * window * 4
    compares = n * window * s * 3
    return vmem, compares


def paged_accounting(hq, hd, ps, n_kv):
    vmem = (hq * hd * 4 * 2) + 2 * ps * n_kv * hd * 4 + hq * ps * 4
    flops = 4 * hq * ps * hd
    return vmem, flops


def _roofline_row(rl):
    return [rl.kernel, rl.geometry_label, rl.backend, int(rl.bytes_moved),
            int(rl.flops), f"{rl.intensity:.4f}", f"{rl.peak_fraction:.4f}",
            rl.trusted_peaks]


def bench_record_fused(rows, roofs):
    """Fused record kernel vs the vmapped scatter oracle, per event."""
    for (lanes, nb, w, mine_rows) in [(4, 16, 2, 16), (8, 64, 2, 32)]:
        cfg = MithrilConfig(min_support=2, max_support=4, lookahead=8,
                            rec_buckets=nb, rec_ways=w,
                            mine_rows=mine_rows, pf_buckets=nb, pf_ways=w,
                            prefetch_list=2)
        states = jax.vmap(lambda _: init_state(cfg))(jnp.arange(lanes))
        rng = np.random.default_rng(7)
        n_ev = 24
        blocks = rng.integers(0, 4 * nb, size=(n_ev, lanes)).astype(np.int32)
        ens = rng.integers(0, 2, size=(n_ev, lanes)).astype(bool)

        oracle = fused = states
        t_us = 0.0
        for t in range(n_ev):
            b, e = jnp.asarray(blocks[t]), jnp.asarray(ens[t])
            oracle = jax.vmap(
                lambda s, bb, ee: record_event(cfg, s, bb, ee))(oracle, b, e)
            t0 = time.time()
            fused = ops.mithril_record_fused(fused, b, e)
            jax.block_until_ready(fused)
            t_us += (time.time() - t0) * 1e6
        ok = all(bool(jnp.array_equal(getattr(oracle, f), getattr(fused, f)))
                 for f in oracle._fields)
        r_sup, s_sup = oracle.rec_ts.shape[-1], oracle.mine_ts.shape[-2]
        geom = dict(lanes=lanes, n_buckets=nb, ways=w, r_sup=r_sup,
                    mine_rows=mine_rows, s_sup=s_sup)
        shape = f"l={lanes},nb={nb},w={w},nm={mine_rows}"
        rl = analyze_kernel("mithril_record_fused", geom)
        rl.geometry_label = shape
        rows.append(["mithril_record_fused", shape, ok,
                     f"{t_us / n_ev:.0f}", int(rl.bytes_moved),
                     int(rl.flops)])
        roofs.append(rl)
        record_kernel("mithril_record_fused", shape, ok, rl.to_dict(),
                      wallclock_us=t_us / n_ev)
        print(f"record l={lanes} nb={nb}: match={ok} "
              f"bytes={rl.bytes_moved / 1024:.0f}KB ai={rl.intensity:.3f} "
              f"interp={t_us / n_ev:.0f}us/event")


def bench_mine(rows, roofs, rng):
    for (n, s, window) in [(256, 8, 32), (1024, 8, 64), (4096, 8, 100)]:
        cnt = rng.integers(2, s + 1, size=n).astype(np.int32)
        base = np.sort(rng.integers(0, 50 * n, size=n)).astype(np.int32)
        ts = np.zeros((n, s), np.int32)
        for i in range(n):
            c = int(cnt[i])
            ts[i, :c] = np.sort(rng.integers(0, 40, size=c)) + base[i]
        valid = jnp.ones((n,), bool)
        args = (jnp.array(ts), jnp.array(cnt), valid)
        out_k = ops.mithril_pairwise(*args, 60, window)
        out_r = pairwise_codes(*args, 60, window)
        ok = bool(jnp.all(out_k == out_r))
        t0 = time.time()
        for _ in range(3):
            ops.mithril_pairwise(*args, 60, window).block_until_ready()
        t_k = (time.time() - t0) / 3
        vmem, comp = mine_accounting(n, s, window)
        shape = f"n={n},w={window}"
        rows.append(["mithril_mine", shape, ok, f"{t_k*1e6:.0f}", vmem, comp])
        rl = analyze_kernel("mithril_mine",
                            dict(mine_rows=n, s_sup=s, window=window))
        rl.geometry_label = shape
        roofs.append(rl)
        record_kernel("mithril_mine", shape, ok, rl.to_dict(),
                      wallclock_us=t_k * 1e6)
        print(f"mine n={n} w={window}: match={ok} vmem={vmem/1024:.0f}KB "
              f"compares={comp/1e6:.1f}M interp={t_k*1e3:.1f}ms")


def bench_hash_lookup(rows, roofs, rng):
    """Prefetch-table probe vs the vmapped jnp oracle (ISSUE 9: the
    probe joins the roofline registry alongside the fused kernels)."""
    from repro.core.hashindex import bucket_of
    from repro.kernels import ref
    for (nq, nb, w, p) in [(256, 128, 4, 3), (512, 256, 4, 3)]:
        pf_key = np.full((nb, w), -1, np.int32)
        pf_vals = np.full((nb, w, p), -1, np.int32)
        keys = rng.choice(100000, nb, replace=False).astype(np.int32)
        for k in keys:
            b = int(bucket_of(jnp.int32(int(k)), nb))
            ways = pf_key[b]
            if (ways == -1).any():
                slot = int(np.argmax(ways == -1))
                pf_key[b, slot] = k
                pf_vals[b, slot] = np.arange(p) + k + 1
        qs = np.concatenate([keys[: nq // 2],
                             rng.integers(2 * 10**5, 3 * 10**5, nq - nq // 2)
                             ]).astype(np.int32)
        args = (jnp.array(qs), jnp.array(pf_key), jnp.array(pf_vals))
        got = ops.prefetch_lookup(*args)
        want = ref.hash_lookup_ref(*args)
        ok = bool(jnp.array_equal(got, want))
        t0 = time.time()
        for _ in range(3):
            ops.prefetch_lookup(*args).block_until_ready()
        t_k = (time.time() - t0) / 3
        shape = f"q={nq},nb={nb},w={w},p={p}"
        rl = analyze_kernel("hash_lookup",
                            dict(queries=nq, n_buckets=nb, ways=w, plist=p))
        rl.geometry_label = shape
        rows.append(["hash_lookup", shape, ok, f"{t_k*1e6:.0f}",
                     int(rl.bytes_moved), int(rl.flops)])
        roofs.append(rl)
        record_kernel("hash_lookup", shape, ok, rl.to_dict(),
                      wallclock_us=t_k * 1e6)
        print(f"lookup q={nq} nb={nb}: match={ok} "
              f"bytes={rl.bytes_moved / 1024:.0f}KB ai={rl.intensity:.3f} "
              f"interp={t_k*1e6:.0f}us")


def bench_paged(rows, roofs, rng):
    for (b, hq, hkv, hd, ps, npg) in [(4, 32, 8, 128, 16, 8),
                                      (8, 16, 4, 64, 32, 16)]:
        npt = npg * b + 1
        key = jax.random.PRNGKey(0)
        q = jax.random.normal(key, (b, hq, hd), jnp.float32)
        kp = jax.random.normal(key, (npt, ps, hkv, hd), jnp.float32)
        vp = jax.random.normal(key, (npt, ps, hkv, hd), jnp.float32)
        ptab = jnp.array(rng.choice(npt, (b, npg), replace=False
                                    ).astype(np.int32))
        lens = jnp.full((b,), npg * ps, jnp.int32)
        from repro.kernels import ref
        got = ops.paged_decode(q, kp, vp, ptab, lens)
        want = ref.paged_decode_ref(q, kp, vp, ptab, lens)
        ok = bool(jnp.allclose(got, want, rtol=2e-4, atol=2e-4))
        vmem, flops = paged_accounting(hq, hd, ps, hkv)
        shape = f"b={b},hq={hq},ps={ps}"
        rows.append(["paged_decode", shape, ok, "-", vmem, flops])
        rl = analyze_kernel("paged_decode",
                            dict(batch=b, heads_q=hq, heads_kv=hkv,
                                 head_dim=hd, page_size=ps, n_pages=npg))
        rl.geometry_label = shape
        roofs.append(rl)
        record_kernel("paged_decode", shape, ok, rl.to_dict())
        print(f"paged b={b} hq={hq}: match={ok} vmem/step={vmem/1024:.0f}KB "
              f"flops/page={flops/1e3:.0f}K")


def main():
    rows, roofs = [], []
    rng = np.random.default_rng(0)

    bench_record_fused(rows, roofs)
    bench_mine(rows, roofs, rng)
    bench_hash_lookup(rows, roofs, rng)
    bench_paged(rows, roofs, rng)

    write_csv("kernel_micro.csv",
              "kernel,shape,matches_oracle,interp_us,vmem_bytes,arith", rows)
    write_csv("kernel_roofline.csv", ROOFLINE_HEADER,
              [_roofline_row(rl) for rl in roofs])


def _parser() -> argparse.ArgumentParser:
    return argparse.ArgumentParser(description=__doc__.splitlines()[0])


if __name__ == "__main__":
    _parser().parse_args()
    main()
