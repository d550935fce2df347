"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def make_table(rng, n, s, spread=30):
    cnt = rng.integers(0, s + 2, size=n).astype(np.int32)
    base = np.sort(rng.integers(0, 40 * n, size=n)).astype(np.int32)
    ts = np.zeros((n, s), np.int32)
    for i in range(n):
        c = min(int(cnt[i]), s)
        if c:
            ts[i, :c] = np.sort(rng.integers(0, spread, size=c)) + base[i]
    valid = (cnt >= 2) & (cnt <= s)
    return jnp.array(ts), jnp.array(cnt), jnp.array(valid)


class TestMineKernel:
    @pytest.mark.parametrize("n,s,delta,window",
                             [(64, 4, 8, 8), (96, 8, 25, 16),
                              (256, 8, 60, 32), (100, 12, 100, 48),
                              (33, 4, 5, 7)])
    def test_matches_oracle(self, rng, n, s, delta, window):
        ts, cnt, valid = make_table(rng, n, s)
        got = ops.mithril_pairwise(ts, cnt, valid, delta, window)
        want = ref.mithril_pairwise_ref(ts, cnt, valid, delta, window)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_all_invalid_rows(self):
        ts = jnp.zeros((32, 4), jnp.int32)
        cnt = jnp.zeros((32,), jnp.int32)
        valid = jnp.zeros((32,), bool)
        got = ops.mithril_pairwise(ts, cnt, valid, 10, 8)
        assert int(jnp.sum(got)) == 0


class TestHashLookupKernel:
    @pytest.mark.parametrize("nb,w,p,nq", [(64, 4, 2, 64), (256, 4, 3, 100),
                                           (32, 2, 2, 7)])
    def test_matches_oracle(self, rng, nb, w, p, nq):
        from repro.core.hashindex import bucket_of
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
        got = ops.prefetch_lookup(jnp.array(qs), jnp.array(pf_key),
                                  jnp.array(pf_vals))
        want = ref.hash_lookup_ref(jnp.array(qs), jnp.array(pf_key),
                                   jnp.array(pf_vals))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


class TestPagedDecodeKernel:
    @pytest.mark.parametrize("b,hq,hkv,hd,ps,npg,dtype",
                             [(2, 8, 2, 32, 16, 4, jnp.float32),
                              (1, 4, 4, 64, 32, 8, jnp.float32),
                              (3, 16, 8, 64, 8, 6, jnp.bfloat16),
                              (2, 4, 1, 128, 64, 2, jnp.float32)])
    def test_matches_oracle(self, rng, b, hq, hkv, hd, ps, npg, dtype):
        np_total = npg * b + 2
        key = jax.random.PRNGKey(0)
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (b, hq, hd), dtype)
        kp = jax.random.normal(ks[1], (np_total, ps, hkv, hd), dtype)
        vp = jax.random.normal(ks[2], (np_total, ps, hkv, hd), dtype)
        ptab = jnp.array(
            rng.choice(np_total, (b, npg), replace=False).astype(np.int32))
        lengths = jnp.array(rng.integers(1, npg * ps + 1, b).astype(np.int32))
        got = ops.paged_decode(q, kp, vp, ptab, lengths)
        want = ref.paged_decode_ref(q, kp, vp, ptab, lengths)
        tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=tol, atol=tol)

    def test_kernel_agrees_with_mine_plus_pairwise(self, rng):
        """Kernel pairwise codes slot into associations_dense unchanged."""
        from repro.core.mining import associations_dense
        ts, cnt, valid = make_table(rng, 64, 8)
        a = associations_dense(jnp.arange(64, dtype=jnp.int32) + 100,
                               ts.T, cnt, 2, 8, 20, 16, 128)
        b_ = associations_dense(jnp.arange(64, dtype=jnp.int32) + 100,
                                ts.T, cnt, 2, 8, 20, 16, 128,
                                pairwise_fn=lambda t, c, v, d, w:
                                ops.mithril_pairwise(t, c, v, d, w))
        for x, y in zip(a, b_):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
