"""Pallas TPU kernel for the MITHRIL pairwise association check.

The mining hot-spot is the (rows x window x S) timestamp comparison after
the sort (core/mining.pairwise_codes). TPU-native design (DESIGN.md §2):

* the whole (padded) timestamp matrix lives in VMEM — mining tables are
  small by construction (paper: 1250 rows x S=8 -> ~40KB at int32), far
  under the ~16MB VMEM budget;
* the grid tiles ROWS; each program compares its (BLK, S) row tile
  against ``window`` STATICALLY-SHIFTED row slabs, so the inner loop is
  pure VPU elementwise compares over lanes — no gathers, no dynamic
  control flow;
* ``window`` is the paper's Delta-bounded inner-loop break, here a static
  bound (first timestamps are unique, so at most Delta rows qualify).

Input rows must be pre-padded with ``window`` trailing invalid rows
(ops.py does this), keeping every shifted slice in range.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import default_interpret


def _offset_code(ts_i, cnt_i, val_i, live_i, ts_j, cnt_j, val_j,
                 delta: int):
    """Association codes for one shifted slab: (BLK, 1) int32 0/1/2.

    Same math and tie-breaking as ``core.mining.pairwise_codes``.
    Every reduction runs
    over int32 0/1 masks (no bool reductions, no bool<->int casts),
    which is what Mosaic lowers.
    """
    gap_ok = (ts_j[:, :1] - ts_i[:, :1]) <= delta
    same_cnt = cnt_j == cnt_i
    d = ts_j - ts_i
    diffs = jnp.where(d < 0, -d, d)
    too_far = jnp.max(jnp.where(live_i & (diffs > delta), 1, 0), axis=1,
                      keepdims=True)
    adjacent = jnp.max(jnp.where(live_i & (diffs == 1), 1, 0), axis=1,
                       keepdims=True)
    weak = too_far == 0
    strong = weak & (adjacent == 1)
    ok = (val_i == 1) & (val_j == 1) & gap_ok & same_cnt
    return jnp.where(ok & strong, 2, jnp.where(ok & weak, 1, 0))


def _codes_tile(ts_ref, cnt_ref, valid_ref, r0, *, blk: int, window: int,
                delta: int):
    """(BLK, window) codes of rows ``r0 .. r0+BLK`` against their
    ``window`` successors. A ``fori_loop`` over the offsets keeps one
    shifted slab live at a time; the tile is carried in registers and
    stored once by the caller."""
    def rows(ref, start):
        return ref[pl.ds(start, blk), :]

    ts_i, cnt_i, val_i = (rows(ts_ref, r0), rows(cnt_ref, r0),
                          rows(valid_ref, r0))      # (BLK, S), (BLK, 1) x2
    s = ts_i.shape[1]
    live_i = jax.lax.broadcasted_iota(jnp.int32, (blk, s), 1) < cnt_i
    col = jax.lax.broadcasted_iota(jnp.int32, (blk, window), 1)

    def body(b, out):
        j = r0 + 1 + b
        code = _offset_code(ts_i, cnt_i, val_i, live_i, rows(ts_ref, j),
                            rows(cnt_ref, j), rows(valid_ref, j), delta)
        return jnp.where(col == b, code, out)

    return jax.lax.fori_loop(0, window, body,
                             jnp.zeros((blk, window), jnp.int32))


def _mine_kernel(ts_ref, cnt_ref, valid_ref, out_ref, *, delta: int,
                 window: int, blk: int):
    """Grid: (n_row_blocks,). ts_ref: full (N_pad, S); out: (BLK, W) tile."""
    r0 = pl.multiple_of(pl.program_id(0) * blk, blk)
    out_ref[...] = _codes_tile(ts_ref, cnt_ref, valid_ref, r0, blk=blk,
                               window=window, delta=delta)


def pairwise_codes_kernel(ts: jax.Array, cnt: jax.Array, valid: jax.Array,
                          delta: int, window: int, *, blk: int = 128,
                          interpret: Optional[bool] = None) -> jax.Array:
    """ts: (N_pad, S) int32 sorted by ts[:,0] and padded with >= window
    invalid rows; cnt/valid: (N_pad, 1) int32. Returns (N, W) codes where
    N = N_pad - window - 1 ... callers slice. See ops.mithril_pairwise.

    ``interpret=None`` resolves from the backend: compiled on TPU,
    interpreted elsewhere (never silently interpreted on real hardware).
    """
    interpret = default_interpret(interpret)
    n_pad, s = ts.shape
    n_rows = n_pad - window - 1
    assert n_rows % blk == 0, (n_rows, blk)
    grid = (n_rows // blk,)
    kernel = functools.partial(_mine_kernel, delta=delta, window=window,
                               blk=blk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(ts.shape, lambda i: (0, 0)),      # whole table VMEM
            pl.BlockSpec(cnt.shape, lambda i: (0, 0)),
            pl.BlockSpec(valid.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((blk, window), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, window), jnp.int32),
        interpret=interpret,
        name="mithril_mine",
    )(ts, cnt, valid)
