"""MITHRIL mining procedure.

Two implementations with identical semantics:

* ``associations_dense`` — vectorized JAX version used under jit. After an
  XLA stable sort by first timestamp, every row ``i`` is compared against a
  bounded look-ahead window of rows ``j = i+1 .. i+W`` (the paper's inner
  loop breaks once ``T[j][0] - T[i][0] > Delta``; first timestamps are
  unique so ``W = min(rows-1, Delta)`` is safe). The pairwise check is a
  dense ``(rows, W, S)`` broadcast — this is the compute hot-spot that the
  Pallas kernel in ``repro.kernels.mithril_mine`` tiles for VMEM.

* ``mine_reference_sequential`` — a literal numpy transcription of the
  paper's Algorithms 1 & 2, used as the test oracle.

Association semantics (paper Fig. 2 + Alg. 1):
  rows must have the SAME number of timestamps; every aligned timestamp
  pair must differ by at most ``Delta`` (weak); at least one pair with
  difference exactly 1 upgrades the pair to strong. Alg. 2 then keeps, per
  source row, the FIRST association found plus every STRONG association.
"""

from __future__ import annotations

from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

INT32_MAX = np.iinfo(np.int32).max


# ---------------------------------------------------------------------------
# Vectorized (jit) implementation
# ---------------------------------------------------------------------------

def sort_by_first_ts(blocks: jax.Array, ts: jax.Array, cnt: jax.Array,
                     min_support: int, max_support: int):
    """Stable-sort mining rows by first timestamp; invalid rows sink to the end.

    A row is valid if ``R <= cnt <= S`` (cnt > S marks a frequent block the
    paper kicks out; cnt < R cannot normally occur but guards cleared rows).
    """
    valid = (cnt >= min_support) & (cnt <= max_support)
    key = jnp.where(valid, ts[:, 0], INT32_MAX)
    order = jnp.argsort(key, stable=True)
    return blocks[order], ts[order], cnt[order], valid[order]


def pairwise_codes(ts: jax.Array, cnt: jax.Array, valid: jax.Array,
                   delta: int, window: int) -> jax.Array:
    """Association codes for each (row i, offset d=1..window): 0/1/2 = none/weak/strong.

    Pure-jnp oracle for the Pallas kernel (same math, same tie-breaking).
    ``ts``: (N, S) int32 sorted by ts[:,0]; ``cnt``: (N,) int32.
    """
    n, s = ts.shape
    idx_j = jnp.arange(n)[:, None] + jnp.arange(1, window + 1)[None, :]   # (N, W)
    in_range = idx_j < n
    idx_jc = jnp.minimum(idx_j, n - 1)
    ts_j = ts[idx_jc]                    # (N, W, S)
    cnt_j = cnt[idx_jc]                  # (N, W)
    valid_j = valid[idx_jc] & in_range

    # paper inner-loop break: first-timestamp gap within Delta
    gap_ok = (ts_j[:, :, 0] - ts[:, None, 0]) <= delta
    same_cnt = cnt_j == cnt[:, None]

    diffs = jnp.abs(ts_j - ts[:, None, :])                     # (N, W, S)
    k = jnp.arange(s)[None, None, :]
    live = k < cnt[:, None, None]                              # aligned pairs only
    weak = jnp.all(jnp.where(live, diffs <= delta, True), axis=-1)
    strong = weak & jnp.any(jnp.where(live, diffs == 1, False), axis=-1)

    ok = valid[:, None] & valid_j & gap_ok & same_cnt
    code = jnp.where(ok & strong, 2, jnp.where(ok & weak, 1, 0))
    return code.astype(jnp.int32)


def select_pairs(code: jax.Array) -> jax.Array:
    """Alg. 2 selection: per row keep every strong pair plus the first pair.

    Returns a bool mask (N, W).
    """
    any_assoc = code > 0
    first_d = jnp.argmax(any_assoc, axis=1)                     # first offset w/ assoc
    has_any = jnp.any(any_assoc, axis=1)
    w = code.shape[1]
    is_first = (jnp.arange(w)[None, :] == first_d[:, None]) & has_any[:, None]
    return (code == 2) | (is_first & any_assoc)


def _emit_pairs(blk: jax.Array, code: jax.Array, max_pairs: int):
    """Alg. 2 selection + compaction: codes (N, W) -> (src, dst, valid, dropped).

    Pairs are compacted to ``max_pairs`` in the paper's discovery order
    (source-row-major, then ascending distance).
    """
    mask = select_pairs(code)
    n, w = mask.shape
    idx_j = jnp.minimum(jnp.arange(n)[:, None] + jnp.arange(1, w + 1)[None, :], n - 1)
    src = jnp.broadcast_to(blk[:, None], (n, w)).reshape(-1)
    dst = blk[idx_j].reshape(-1)
    flat = mask.reshape(-1)

    # stable compaction: flagged pairs first, original (discovery) order kept
    order = jnp.argsort(~flat, stable=True)[:max_pairs]
    return (src[order], dst[order], flat[order],
            jnp.maximum(jnp.sum(flat) - max_pairs, 0))


def associations_dense(blocks: jax.Array, ts: jax.Array, cnt: jax.Array,
                       min_support: int, max_support: int, delta: int,
                       window: int, max_pairs: int,
                       pairwise_fn=pairwise_codes):
    """Full vectorized mining: returns (src, dst, valid_mask, n_dropped).

    ``pairwise_fn`` is swappable so the Pallas kernel can slot in for the
    hot inner loop (``kernels.ops.mithril_pairwise``).
    """
    blk, tss, cnts, valid = sort_by_first_ts(blocks, ts, cnt, min_support, max_support)
    code = pairwise_fn(tss, cnts, valid, delta, window)
    return _emit_pairs(blk, code, max_pairs)


# ---------------------------------------------------------------------------
# Sequential reference (paper Algorithms 1 & 2, verbatim) — test oracle
# ---------------------------------------------------------------------------

def _check_association(row_i: np.ndarray, row_j: np.ndarray, delta: int,
                       threshold: str) -> bool:
    """Paper Algorithm 1. Rows are 1-D arrays of timestamps (trimmed to cnt)."""
    if len(row_i) != len(row_j):
        return False
    diffs = np.abs(row_j - row_i)
    if np.any(diffs > delta):
        return False
    strong = bool(np.any(diffs == 1))
    if threshold == "strong":
        return strong
    return True  # weak suffices


def mine_reference_sequential(blocks: np.ndarray, ts: np.ndarray, cnt: np.ndarray,
                              min_support: int, max_support: int,
                              delta: int) -> List[Tuple[int, int]]:
    """Paper Algorithm 2 on a raw (unsorted) mining table. Returns directed
    (src_block, dst_block) pairs in discovery order."""
    valid = (cnt >= min_support) & (cnt <= max_support)
    key = np.where(valid, ts[:, 0], INT32_MAX)
    order = np.argsort(key, kind="stable")
    blk, tss, cnts, val = blocks[order], ts[order], cnt[order], valid[order]

    pairs: List[Tuple[int, int]] = []
    n = len(blk)
    for i in range(n - 1):
        if not val[i]:
            continue
        threshold = "weak"
        row_i = tss[i, : cnts[i]]
        for j in range(i + 1, n):
            # invalid rows sort to the end with key INT32_MAX, so the paper's
            # single break-on-gap condition covers them too
            if not val[j] or tss[j, 0] - tss[i, 0] > delta:
                break
            if _check_association(row_i, tss[j, : cnts[j]], delta, threshold):
                pairs.append((int(blk[i]), int(blk[j])))
                threshold = "strong"
    return pairs
