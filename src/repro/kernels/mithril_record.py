"""Pallas TPU kernel: the fused per-request MITHRIL record path.

The branchless scatter form of ``core.mithril.record_event`` (DESIGN.md
§7) still leaves XLA to emit one gather + one ``.at[].set`` scatter per
state leaf per request — eleven separate HBM round trips through the
recording and mining tables for every recorded event. This kernel fuses
the whole record path — the ``hashindex.locate`` probe, the
recording-table circular-buffer timestamp stamp, and the
mining/prefetch-metadata table insert (migration) — into ONE launch per
request slab: grid ``(lanes,)``. Memory layout, probe sequence and
padded-lane masking are documented in DESIGN.md §11.

Table layout inside the kernel (per lane; the wrapper transposes):

* recording table — lane-dense: ``rec_key/cnt/age/loc/row`` as
  ``(W, NB)`` (ways on sublanes, buckets on the 128-wide lane axis) and
  ``rec_ts`` as ``(W, R, NB)``. The probed bucket ``b`` is computed by
  the wrapper and scalar-prefetched, so each program's BlockSpec DMAs only the
  ``win``-wide bucket window holding ``b`` — never the whole table —
  and the kernel addresses column ``b % win`` with a one-hot lane mask;
* mining table — ``mine_block/cnt`` as ``(1, Nm)`` rows and ``mine_ts``
  as ``(S, Nm)``, the whole lane's table per program (the touched row
  ``m`` depends on table contents, so it is a one-hot lane mask too);
* scalars — ``block/enabled/bucket/mine_fill/ts`` live in SMEM (scalar
  prefetch); the new ``mine_fill`` comes back as a lane-dense
  ``(1, 128)`` broadcast row, ``ts`` is advanced by the wrapper.

Every probe result is a ``(1, 1)`` vector (masked min/sum reductions,
int32 throughout), so no value moves from the vector unit to the
scalar unit. ``enabled == 0`` lanes (padded tails, gated record
policies) write every touched element back with its old contents — the
same bit-exact no-op contract as the scatter form. Outputs alias inputs
(``input_output_aliases``): only the bucket window is written, the rest
of each table stays in place. Bit-identity against ``record_event`` is
pinned per event by ``tests/test_record_kernel.py`` (interpret mode),
and the kernel is compiled for a v5e at real table sizes by
``tests/test_tpu_compile.py``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import default_interpret

LANE_TILE = 128


def bucket_window(n_buckets: int) -> int:
    """Width of the bucket window one program touches: one 128-lane
    tile, or the whole table when it is narrower or does not tile."""
    if n_buckets > LANE_TILE and n_buckets % LANE_TILE == 0:
        return LANE_TILE
    return n_buckets


def _first_true(mask, iota, width):
    """(1, 1) index of the first True along sublanes (``width`` if none)
    — ``jnp.argmax(mask)`` first-hit semantics as a masked min."""
    return jnp.min(jnp.where(mask, iota, width), axis=0, keepdims=True)


def _record_kernel(blk_ref, en_ref, bkt_ref, fill_ref, ts_ref,
                   rec_key_ref, rec_ts_ref, rec_cnt_ref, rec_age_ref,
                   rec_loc_ref, rec_row_ref, mine_block_ref, mine_ts_ref,
                   mine_cnt_ref,
                   o_rec_key, o_rec_ts, o_rec_cnt, o_rec_age, o_rec_loc,
                   o_rec_row, o_mine_block, o_mine_ts, o_mine_cnt, o_fill,
                   *, ways: int, win: int, r_sup: int, s_sup: int):
    """Grid: (lanes,). Table refs carry a leading lane dim of 1."""
    i32 = jnp.int32
    lane = pl.program_id(0)

    def vec(x):             # SMEM scalar -> (1, 1) int32 vector
        return jnp.full((1, 1), x, i32)

    blk = vec(blk_ref[lane])
    en = vec(en_ref[lane]) != 0
    ts = vec(ts_ref[lane])
    fill = vec(fill_ref[lane])
    col = vec(bkt_ref[lane] % win)

    # --- hashindex.locate: probe the bucket, pick hit way or victim ---
    keys = rec_key_ref[0]                                 # (W, win)
    age = rec_age_ref[0]
    cnt = rec_cnt_ref[0]
    loc = rec_loc_ref[0]
    row = rec_row_ref[0]
    at_b = jax.lax.broadcasted_iota(i32, (ways, win), 1) == col
    way_of = jax.lax.broadcasted_iota(i32, (ways, win), 0)

    def bucket(tile):       # the probed bucket's (W, 1) column
        return jnp.sum(jnp.where(at_b, tile, 0), axis=1, keepdims=True)

    kw = jax.lax.broadcasted_iota(i32, (ways, 1), 0)
    kcol, acol = bucket(keys), bucket(age)
    hit = kcol == blk
    found = jnp.max(jnp.where(hit, 1, 0), axis=0, keepdims=True) > 0
    way_hit = _first_true(hit, kw, ways)
    empty = kcol == -1
    any_empty = jnp.max(jnp.where(empty, 1, 0), axis=0, keepdims=True) > 0
    first_empty = _first_true(empty, kw, ways)
    oldest = _first_true(acol == jnp.min(acol, axis=0, keepdims=True),
                         kw, ways)
    w = jnp.where(found, way_hit, jnp.where(any_empty, first_empty, oldest))
    at_bw = at_b & (way_of == w)

    def pick(tile):         # the (b, w) element as a (1, 1) vector
        return jnp.sum(jnp.sum(jnp.where(at_bw, tile, 0), axis=1,
                               keepdims=True), axis=0, keepdims=True)

    old_cnt, old_loc, old_row = pick(cnt), pick(loc), pick(row)
    in_mine = old_loc == 1
    is_new = en & ~found
    is_rec = en & found & ~in_mine
    is_upd = en & found & in_mine

    # --- recording-table circular-buffer stamp (way w's R timestamps) ---
    tsr = rec_ts_ref[0]                                   # (W, R, win)
    ts_shape = (ways, r_sup, win)
    k_of = jax.lax.broadcasted_iota(i32, ts_shape, 1)
    at_ts = (jax.lax.broadcasted_iota(i32, ts_shape, 2) == col) \
        & (jax.lax.broadcasted_iota(i32, ts_shape, 0) == w)
    stamped = jnp.where(
        is_new, jnp.where(k_of == 0, ts, 0),
        jnp.where(is_rec & (k_of == old_cnt), ts, tsr))
    new_tsr = jnp.where(at_ts, stamped, tsr)
    ts_row = [jnp.sum(jnp.where(at_ts & (k_of == j), new_tsr, 0),
                      axis=(0, 1, 2), keepdims=True)[0]
              for j in range(r_sup)]                      # (1, 1) each
    cnt_val = jnp.where(is_new, 1, old_cnt + jnp.where(is_rec, 1, 0))
    migrate = is_rec & (cnt_val >= r_sup)
    if r_sup == 1:          # static branch: new rows are born mining-ready
        migrate = migrate | is_new

    # --- mining-table insert (row m, a one-hot lane mask) ---
    m = jnp.where(migrate, fill, jnp.where(is_upd, old_row, 0))
    mblk = mine_block_ref[0]                              # (1, Nm)
    mcnt = mine_cnt_ref[0]
    mts = mine_ts_ref[0]                                  # (S, Nm)
    nm = mblk.shape[1]
    at_m = jax.lax.broadcasted_iota(i32, (1, nm), 1) == m
    old_mcnt = jnp.sum(jnp.where(at_m, mcnt, 0), axis=1, keepdims=True)
    can = old_mcnt < s_sup
    pos = jnp.minimum(old_mcnt, s_sup - 1)
    s_iota = jax.lax.broadcasted_iota(i32, (s_sup, nm), 0)
    mig_ts = mts
    for j in range(r_sup):  # static unroll: R is a small table param
        mig_ts = jnp.where(s_iota == j, ts_row[j], mig_ts)
    upd_ts = jnp.where((s_iota == pos) & can, ts, mts)
    new_mts = jnp.where(migrate, mig_ts, jnp.where(is_upd, upd_ts, mts))
    at_m_s = jax.lax.broadcasted_iota(i32, (s_sup, nm), 1) == m

    # --- stores (disabled events store the old values) ---
    o_rec_key[0] = jnp.where(at_bw & is_new, blk, keys)
    o_rec_ts[0] = new_tsr
    o_rec_cnt[0] = jnp.where(at_bw, cnt_val, cnt)
    o_rec_age[0] = jnp.where(at_bw & is_new, ts, age)
    o_rec_loc[0] = jnp.where(
        at_bw, jnp.where(migrate, 1, jnp.where(is_new, 0, old_loc)), loc)
    o_rec_row[0] = jnp.where(at_bw, jnp.where(migrate, fill, old_row), row)
    o_mine_block[0] = jnp.where(at_m & migrate, blk, mblk)
    o_mine_ts[0] = jnp.where(at_m_s, new_mts, mts)
    # exceeding S marks the block frequent (excluded from mining)
    o_mine_cnt[0] = jnp.where(
        at_m, jnp.where(migrate, r_sup,
                        jnp.where(is_upd,
                                  jnp.where(can, old_mcnt + 1, s_sup + 1),
                                  mcnt)), mcnt)
    o_fill[0] = jnp.broadcast_to(fill + jnp.where(migrate, 1, 0),
                                 (1, LANE_TILE))


def record_step_kernel(block: jax.Array, enabled: jax.Array,
                       bucket: jax.Array, mine_fill: jax.Array,
                       ts: jax.Array, rec_key: jax.Array,
                       rec_ts: jax.Array, rec_cnt: jax.Array,
                       rec_age: jax.Array, rec_loc: jax.Array,
                       rec_row: jax.Array, mine_block: jax.Array,
                       mine_ts: jax.Array, mine_cnt: jax.Array, *,
                       interpret: Optional[bool] = None):
    """One fused record event for every lane.

    ``block``/``enabled``/``bucket``/``mine_fill``/``ts``: (L,) int32
    (``bucket = mix32(block) & (NB - 1)``); ``rec_key/cnt/age/loc/row``:
    (L, W, NB); ``rec_ts``: (L, W, R, NB); ``mine_block/cnt``: (L, 1, Nm);
    ``mine_ts``: (L, S, Nm). Returns the nine updated tables in the same
    order and layout plus the new ``mine_fill`` as (L, 1, 128) rows
    (``ops.mithril_record_fused`` adapts ``MithrilState``).
    ``interpret=None``: compiled on TPU, interpreted elsewhere.
    """
    interpret = default_interpret(interpret)
    lanes, ways, nb = rec_key.shape
    r_sup = rec_ts.shape[2]
    s_sup, nm = mine_ts.shape[1:]
    win = bucket_window(nb)
    kernel = functools.partial(_record_kernel, ways=ways, win=win,
                               r_sup=r_sup, s_sup=s_sup)

    def window(*rows):
        return pl.BlockSpec((1, *rows, win),
                            lambda i, blk, en, bkt, fill, ts:
                            (i, *(0,) * len(rows), bkt[i] // win))

    def whole(rows):
        return pl.BlockSpec((1, rows, nm),
                            lambda i, blk, en, bkt, fill, ts: (i, 0, 0))

    tables = [window(ways), window(ways, r_sup), window(ways),
              window(ways), window(ways), window(ways), whole(1),
              whole(s_sup), whole(1)]
    fill_spec = pl.BlockSpec((1, 1, LANE_TILE),
                             lambda i, blk, en, bkt, fill, ts: (i, 0, 0))
    sds = jax.ShapeDtypeStruct
    i32 = jnp.int32
    tabs = (rec_key, rec_ts, rec_cnt, rec_age, rec_loc, rec_row,
            mine_block, mine_ts, mine_cnt)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(lanes,), in_specs=tables,
            out_specs=tables + [fill_spec]),
        out_shape=[sds(t.shape, i32) for t in tabs]
        + [sds((lanes, 1, LANE_TILE), i32)],
        # tables update in place: input i+5 -> output i
        input_output_aliases={i + 5: i for i in range(len(tabs))},
        interpret=interpret,
        name="mithril_record",
    )(block, enabled, bucket, mine_fill, ts, *tabs)
