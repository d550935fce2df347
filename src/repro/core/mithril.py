"""MITHRIL prefetching layer — functional JAX implementation (paper Alg. 3).

Public API (all pure, jit/scan-safe):

    state = init(cfg)
    state = record(cfg, state, block)            # rFlag path; auto-mines when full
    cand  = lookup(cfg, state, block)            # pFlag path; (P,) block ids or EMPTY
    state, cand = access(cfg, state, block, do_record, do_lookup)
    state = mine(cfg, state)                     # usually triggered by record()
    states = mine_batched(cfg, states, need)     # flagged lanes, one by one

The recording table is set-associative with in-bucket storage; migration to
the mining table happens when a block accumulates ``min_support`` timestamps;
a full mining table triggers ``mine`` which writes discovered associations
into the prefetching table (Sec. 4.2). ``pairwise_fn`` lets the Pallas
kernel replace the dense association check.

Record/mine split contract
--------------------------
``record_event`` advances the recording/mining tables but NEVER runs the
mining procedure; callers MUST call :func:`maybe_mine` before the next
recording event. The mining table holds at most ``mine_rows`` rows and the
migration scatter relies on ``mine_fill < mine_rows`` at entry. ``record``
composes the two for serial callers; the batched sweep engine
(``cache/sweep.py``) keeps them apart so mining can run at batch level.

Branchless scatter form (DESIGN.md §7)
--------------------------------------
The record/association hot path used to dispatch through ``lax.cond`` /
``lax.switch``. Under ``vmap`` those lower to selects that copy every
recording/prefetch table per lane per request — the overhead-vs-benefit
trap the paper's cost argument (Sec. 4.2) exists to avoid. The functions
below instead compute the (bucket, way, row-value) updates for every case
unconditionally, select between the *scalars/rows*, and apply exactly one
``.at[bucket, way].set(row)`` scatter per table. A disabled event writes
each slot's old value back — bit-identical to not running at all — which
is what lets ``simulator.py`` drop its per-segment ``lax.cond`` wrappers.
``tests/test_record_scatter.py`` asserts per-event bit-equivalence against
a frozen copy of the cond/switch implementation.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax

from .config import MithrilConfig
from .hashindex import EMPTY, locate, probe
from .mining import associations_dense, pairwise_codes
from .state import MithrilState, init_state

init = init_state


# ---------------------------------------------------------------------------
# Prefetching table
# ---------------------------------------------------------------------------

def lookup(cfg: MithrilConfig, state: MithrilState, block: jax.Array) -> jax.Array:
    """Return up to P prefetch candidates for ``block`` (EMPTY-padded).

    Pure read (pFlag path): never touches state, so it needs no mining
    barrier and may be called at any point of the record/maybe_mine cycle.

    The P slots are read as P scalar gathers, not as one ``(P,)`` row
    (``pf_vals[b, way]``, same values): a row gather wants the table laid
    out with P minor-most, while the sweep's scan carries it in the
    layout the mining branch writes (buckets minor), so the TPU compiler
    would relayout the whole table into padded ``(PW, P)`` tiles, 64
    times its bytes at P=2, on every step (PERF.md section 5).
    """
    b, way, found = probe(state.pf_key, block, cfg.pf_buckets)
    vals = jnp.stack([state.pf_vals[b, way, p]
                      for p in range(cfg.prefetch_list)])
    return jnp.where(found, vals, jnp.full((cfg.prefetch_list,), EMPTY, jnp.int32))


def assoc_count(cfg: MithrilConfig, state: MithrilState,
                block: jax.Array) -> jax.Array:
    """Associations recorded with ``block`` as source (0 when absent).

    Pure read of the prefetching table like :func:`lookup` — safe at any
    point of the record/maybe_mine cycle. Feeds the learned policy's
    association-count feature (DESIGN.md §12): how sporadic-association
    mining has weighted this block so far.
    """
    b, way, found = probe(state.pf_key, block, cfg.pf_buckets)
    return jnp.where(found, state.pf_cnt[b, way], jnp.int32(0))


def add_association(cfg: MithrilConfig, state: MithrilState,
                    src: jax.Array, dst: jax.Array,
                    valid: jax.Array) -> MithrilState:
    """Insert association src -> dst (FIFO within the P-slot list).

    Branchless scatter form: the update-existing / insert-new / invalid
    cases all reduce to one row write per prefetch-table array at
    ``(bucket, way)``. With ``valid=False`` every slot is written back
    with its old value (bit-exact no-op), so the mining scan needs no
    per-pair ``lax.cond``.
    """
    i32 = jnp.int32
    b, w, found = locate(state.pf_key, state.pf_age, src, cfg.pf_buckets)
    upd = valid & found           # existing source row
    new = valid & ~found          # allocate (or evict into) a fresh row

    old_key, old_vals = state.pf_key[b, w], state.pf_vals[b, w]
    old_cnt, old_age = state.pf_cnt[b, w], state.pf_age[b, w]

    already = upd & jnp.any(old_vals == dst)        # duplicate destination
    pos = jnp.mod(old_cnt, cfg.prefetch_list)       # FIFO ring slot
    kp = jnp.arange(cfg.prefetch_list)
    vals_upd = jnp.where((kp == pos) & ~already, dst, old_vals)
    vals_new = jnp.where(kp == 0, dst, EMPTY)
    stored = (upd & ~already) | new                 # a pair actually landed

    return state._replace(
        pf_key=state.pf_key.at[b, w].set(jnp.where(new, src, old_key)),
        pf_vals=state.pf_vals.at[b, w].set(
            jnp.where(upd, vals_upd, jnp.where(new, vals_new, old_vals))),
        pf_cnt=state.pf_cnt.at[b, w].set(
            jnp.where(new, 1, old_cnt + (upd & ~already).astype(i32))),
        # touch the entry age on every valid update: a re-mined source is
        # hot, and without the refresh choose_victim evicts exactly the
        # hottest sources first (oldest insertion timestamps)
        pf_age=state.pf_age.at[b, w].set(jnp.where(valid, state.ts, old_age)),
        n_pairs=state.n_pairs + stored.astype(i32),
    )


# ---------------------------------------------------------------------------
# Mining
# ---------------------------------------------------------------------------

def _clear_after_mine(state: MithrilState, dropped: jax.Array) -> MithrilState:
    """Clear the mining table and drop stale recording-index pointers."""
    return state._replace(
        rec_key=jnp.where(state.rec_loc == 1, EMPTY, state.rec_key),
        rec_loc=jnp.zeros_like(state.rec_loc),
        mine_block=jnp.full_like(state.mine_block, EMPTY),
        mine_ts=jnp.zeros_like(state.mine_ts),
        mine_cnt=jnp.zeros_like(state.mine_cnt),
        mine_fill=jnp.zeros_like(state.mine_fill),
        n_mines=state.n_mines + 1,
        n_dropped=state.n_dropped + dropped,
    )


def _fold_pairs(cfg: MithrilConfig, state: MithrilState, src, dst, valid,
                dropped) -> MithrilState:
    """Scan discovered pairs into the prefetch table, then clear."""
    def body(st: MithrilState, xs):
        s, d, v = xs
        st = add_association(cfg, st, s, d, v)
        if cfg.symmetric:  # beyond-paper: bidirectional edges (DESIGN.md §3)
            st = add_association(cfg, st, d, s, v)
        return st, None

    state, _ = lax.scan(body, state, (src, dst, valid))
    return _clear_after_mine(state, dropped)


def mine(cfg: MithrilConfig, state: MithrilState,
         pairwise_fn: Optional[Callable] = None) -> MithrilState:
    """Run the mining procedure and fold associations into the prefetch table.

    ``pairwise_fn`` (per-lane ``(N,S)`` contract of
    ``mining.pairwise_codes``) lets the Pallas kernel replace the dense
    association check.
    """
    fn = pairwise_fn or pairwise_codes
    src, dst, valid, dropped = associations_dense(
        state.mine_block, state.mine_ts, state.mine_cnt,
        cfg.min_support, cfg.max_support, cfg.lookahead,
        cfg.window, cfg.pairs_cap, pairwise_fn=fn)
    return _fold_pairs(cfg, state, src, dst, valid, dropped)


def mine_batched(cfg: MithrilConfig, states: MithrilState, need: jax.Array,
                 pairwise_fn: Optional[Callable] = None) -> MithrilState:
    """Mine every lane flagged in ``need``; other lanes are untouched.

    ``states`` is a stacked :class:`MithrilState` with a leading ``(B,)``
    lanes axis (the sweep engine's carry); ``need`` is a ``(B,)`` bool.
    A ``lax.while_loop`` (meant to run *outside* any vmap) takes the
    first lane still flagged, extracts it, runs the serial :func:`mine`
    on it (with ``pairwise_fn``, the per-lane contract of
    ``mining.pairwise_codes``, e.g. the row-block Pallas kernel
    ``kernels.ops.mithril_pairwise`` on TPU), writes it back and clears
    its flag, until no flag is left: mining work in proportion to the
    lanes mined, whatever the batch width. Per-lane results are
    bit-identical to calling :func:`mine` on exactly the needed lanes
    (``tests/test_record_scatter.py``, ``tests/test_sweep.py``).

    Each pass folds its pairs into ONE lane's unbatched prefetch table.
    A fold vmapped over the lanes axis would make the TPU compiler
    relayout every lane's ``pf_key``/``pf_vals``/``pf_age`` on each
    folded pair (DESIGN.md §7).
    """
    def body(carry):
        sts, left = carry
        i = jnp.argmax(left).astype(jnp.int32)
        lane = jax.tree_util.tree_map(lambda x: x[i], sts)
        mined = mine(cfg, lane, pairwise_fn=pairwise_fn)
        sts = jax.tree_util.tree_map(lambda x, v: x.at[i].set(v),
                                     sts, mined)
        return sts, left.at[i].set(False)

    states, _ = lax.while_loop(lambda c: jnp.any(c[1]), body,
                               (states, need))
    return states


# ---------------------------------------------------------------------------
# Recording (branchless scatter form — DESIGN.md §7)
# ---------------------------------------------------------------------------

def record_event(cfg: MithrilConfig, state: MithrilState, block: jax.Array,
                 enabled: jax.Array = True) -> MithrilState:
    """Record one request WITHOUT the mining trigger (rFlag path only).

    Contract: callers MUST follow up with :func:`maybe_mine` before the
    next recording event — the mining table holds at most ``mine_rows``
    rows and the migration scatter relies on it not being full. The split
    exists for the batched sweep engine, which hoists the (rare,
    expensive) mining pass out of the vmapped step to a batch-level
    barrier (DESIGN.md §6).

    ``enabled=False`` makes the event a bit-exact no-op (every slot is
    written back with its old value and ``ts`` does not advance), which
    replaces the ``lax.cond`` wrappers the simulator segments used to
    need — under ``vmap`` those conds copied every table per request.

    The three per-event cases (new block / still recording /
    mining-resident) are computed unconditionally as row values and
    selected as scalars; each table gets exactly one scatter:

      recording table  (bucket, way)    way = probe hit or victim
      mining table     (row,)           row = migration target or rec_row

    Fused Pallas path: on TPU the whole function — probe, stamp and
    mining-table insert — runs as ONE kernel launch per request slab
    (``kernels.mithril_record_fused``, DESIGN.md §11) instead of one
    XLA scatter per table. Batched callers go through
    :func:`record_event_batched`, which keeps this scatter form as the
    off-TPU implementation; the two are bit-identical per event
    (``tests/test_record_kernel.py``), so the contract here — no
    mining, ``enabled=False`` no-op, one write per table — IS the
    kernel's contract.
    """
    i32 = jnp.int32
    r_sup, s_sup = cfg.min_support, cfg.max_support
    enabled = jnp.asarray(enabled)
    ts = state.ts

    b, w, found = locate(state.rec_key, state.rec_age, block, cfg.rec_buckets)
    in_mine = state.rec_loc[b, w] == 1
    is_new = enabled & ~found                 # allocate a recording row
    is_rec = enabled & found & ~in_mine       # append a timestamp in place
    is_upd = enabled & found & in_mine        # timestamps go to the mining row

    old_key, old_ts_row = state.rec_key[b, w], state.rec_ts[b, w]
    old_cnt, old_age = state.rec_cnt[b, w], state.rec_age[b, w]
    old_loc, old_row = state.rec_loc[b, w], state.rec_row[b, w]

    # recording-table row values (invariant: old_cnt < R when is_rec)
    kr = jnp.arange(r_sup)
    ts_row = jnp.where(is_new, jnp.where(kr == 0, ts, 0),
                       jnp.where(is_rec, jnp.where(kr == old_cnt, ts,
                                                   old_ts_row), old_ts_row))
    cnt_val = jnp.where(is_new, 1, old_cnt + is_rec.astype(i32))

    # mining-ready: R timestamps accumulated (immediately, when R == 1)
    migrate = is_rec & (cnt_val >= r_sup)
    if r_sup == 1:  # static branch: new rows are born mining-ready
        migrate = migrate | is_new
    fill = state.mine_fill                    # invariant: fill < mine_rows

    # mining-table row: migration target, the block's resident row, or a
    # no-op write of row 0's old contents
    m = jnp.where(migrate, fill, jnp.where(is_upd, old_row, 0))
    old_mblk, old_mts, old_mcnt = (state.mine_block[m], state.mine_ts[:, m],
                                   state.mine_cnt[m])
    can = old_mcnt < s_sup
    pos = jnp.minimum(old_mcnt, s_sup - 1)
    ks = jnp.arange(s_sup)
    mig_ts = jnp.where(ks < r_sup,
                       jnp.zeros((s_sup,), i32).at[:r_sup].set(ts_row),
                       old_mts)
    upd_ts = jnp.where((ks == pos) & can, ts, old_mts)

    return state._replace(
        rec_key=state.rec_key.at[b, w].set(jnp.where(is_new, block, old_key)),
        rec_ts=state.rec_ts.at[b, w].set(ts_row),
        rec_cnt=state.rec_cnt.at[b, w].set(cnt_val),
        rec_age=state.rec_age.at[b, w].set(jnp.where(is_new, ts, old_age)),
        rec_loc=state.rec_loc.at[b, w].set(
            jnp.where(migrate, 1, jnp.where(is_new, 0, old_loc))),
        rec_row=state.rec_row.at[b, w].set(jnp.where(migrate, fill, old_row)),
        mine_block=state.mine_block.at[m].set(
            jnp.where(migrate, block, old_mblk)),
        mine_ts=state.mine_ts.at[:, m].set(
            jnp.where(migrate, mig_ts, jnp.where(is_upd, upd_ts, old_mts))),
        # exceeding S marks the block frequent (excluded from mining)
        mine_cnt=state.mine_cnt.at[m].set(
            jnp.where(migrate, r_sup,
                      jnp.where(is_upd,
                                jnp.where(can, old_mcnt + 1, s_sup + 1),
                                old_mcnt))),
        mine_fill=fill + migrate.astype(i32),
        ts=ts + enabled.astype(i32),
    )


def record_event_batched(cfg: MithrilConfig, states: MithrilState,
                         blocks: jax.Array, enabled: jax.Array,
                         fused_fn: Optional[Callable] = None
                         ) -> MithrilState:
    """Advance every lane by one recording event (the sweep hot path).

    ``states`` is a stacked :class:`MithrilState` with a leading ``(B,)``
    lanes axis; ``blocks``/``enabled`` are ``(B,)``. Default is the
    vmapped scatter form — exactly what the batched step used to trace —
    and ``fused_fn(states, blocks, enabled)`` swaps in the fused Pallas
    kernel (``kernels.mithril_record_fused``) when the sweep engine's
    backend dispatch (``sweep._batched_record_fn``) selects it on TPU.
    Both implementations are bit-identical per event and inherit the
    :func:`record_event` contract: no mining happens here, so callers
    MUST run the batch-level ``maybe_mine`` barrier before the next
    recording event.
    """
    if fused_fn is not None:
        return fused_fn(states, blocks, enabled)
    enabled = jnp.broadcast_to(jnp.asarray(enabled), blocks.shape)
    return jax.vmap(lambda s, b, e: record_event(cfg, s, b, e))(
        states, blocks, enabled)


def maybe_mine(cfg: MithrilConfig, state: MithrilState,
               pairwise_fn: Optional[Callable] = None) -> MithrilState:
    """Run ``mine`` iff the mining table is full (the Alg. 3 trigger).

    This is the second half of the record/maybe_mine contract: it must
    run between any :func:`record_event` and the next one — whichever
    form the event took (serial scatter, vmapped scatter, or the fused
    Pallas kernel via :func:`record_event_batched`) — restoring the
    ``mine_fill < mine_rows`` invariant the migration write assumes.
    The batched sweep engine runs it as a batch-level ``lax.cond``
    barrier (``sweep.build_batched_step``) rather than per lane.
    """
    return lax.cond(
        state.mine_fill >= cfg.mine_rows,
        functools.partial(mine, cfg, pairwise_fn=pairwise_fn),
        lambda s: s, state)


def record(cfg: MithrilConfig, state: MithrilState, block: jax.Array,
           pairwise_fn: Optional[Callable] = None,
           enabled: jax.Array = True) -> MithrilState:
    """Record one request (Alg. 3 rFlag path); mines when the table fills.

    The serial convenience composition ``record_event`` + ``maybe_mine``
    — use it whenever events are processed one lane at a time; batched
    callers must keep the two halves apart (see :func:`record_event`).
    """
    state = record_event(cfg, state, block, enabled=enabled)
    return maybe_mine(cfg, state, pairwise_fn=pairwise_fn)


def access(cfg: MithrilConfig, state: MithrilState, block: jax.Array,
           do_record: jax.Array, do_lookup: jax.Array,
           pairwise_fn: Optional[Callable] = None):
    """Alg. 3: optional record (rFlag) + optional prefetch lookup (pFlag).

    ``do_record`` gates the recording event branchlessly (no ``lax.cond``
    — a disabled event is a bit-exact no-op) and the composed ``record``
    keeps the record/maybe_mine contract internally.
    """
    state = record(cfg, state, block, pairwise_fn=pairwise_fn,
                   enabled=do_record)
    cand = lookup(cfg, state, block)
    empty = jnp.full_like(cand, EMPTY)
    return state, jnp.where(do_lookup, cand, empty)
