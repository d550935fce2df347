"""Scatter-form record path vs the frozen cond/switch reference.

The tentpole contract (ISSUE 3 / DESIGN.md §7): the branchless
scatter-form implementations of ``mithril.record_event``,
``mithril.add_association``, ``pg.pg_access`` and the cache
``base.access``/``insert_prefetch`` are bit-identical, per event, to the
``lax.cond``/``lax.switch`` implementations they replaced. The replaced
code is kept VERBATIM below as the oracle (the same pattern
``core.mining`` uses with ``mine_reference_sequential``); property tests
drive both over random traces — including the ``min_support == 1``
immediate-migrate branch and the cache's second-chance eviction — and
compare every state leaf after every event. The same file keeps the
row-gather ``mithril.lookup`` that the per-slot read replaced, and
checks the two agree.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax import lax

from repro.cache import base
from repro.cache.base import CacheState, Evicted
from repro.cache.pg import PgConfig, PgState, init_pg, pg_access
from repro.core import MithrilConfig, init, mine, mine_batched
from repro.core.hashindex import EMPTY, bucket_of, choose_victim, probe
from repro.core.mithril import add_association, lookup, record_event
from repro.core.state import MithrilState


def small_cfg(**kw):
    base = dict(min_support=2, max_support=4, lookahead=8, rec_buckets=16,
                rec_ways=2, mine_rows=8, pf_buckets=16, pf_ways=2,
                prefetch_list=2)
    base.update(kw)
    return MithrilConfig(**base)


def assert_trees_equal(a, b, msg=""):
    for (pa, xa), (pb, xb) in zip(jax.tree_util.tree_leaves_with_path(a),
                                  jax.tree_util.tree_leaves_with_path(b)):
        np.testing.assert_array_equal(
            np.asarray(xa), np.asarray(xb),
            err_msg=f"{msg} leaf {jax.tree_util.keystr(pa)}")


# ---------------------------------------------------------------------------
# Frozen reference: pre-scatter record_event (lax.switch form, PR 2)
# ---------------------------------------------------------------------------

def _migrate_ref(cfg, st, block, b, way, ts_row):
    # the mining timestamps are (S, Nm): mining row ``row`` is a column
    row = st.mine_fill
    mine_ts = st.mine_ts.at[: cfg.min_support, row].set(ts_row)
    return st._replace(
        mine_block=st.mine_block.at[row].set(block),
        mine_ts=mine_ts,
        mine_cnt=st.mine_cnt.at[row].set(cfg.min_support),
        mine_fill=row + 1,
        rec_loc=st.rec_loc.at[b, way].set(1),
        rec_row=st.rec_row.at[b, way].set(row),
    )


def record_event_reference(cfg: MithrilConfig, state: MithrilState,
                           block: jax.Array) -> MithrilState:
    ts = state.ts
    b, way, found = probe(state.rec_key, block, cfg.rec_buckets)
    in_mine = state.rec_loc[b, way] == 1

    def case_new(st):
        v = choose_victim(st.rec_key[b], st.rec_age[b])
        fresh = jnp.zeros((cfg.min_support,), jnp.int32).at[0].set(ts)
        st = st._replace(
            rec_key=st.rec_key.at[b, v].set(block),
            rec_ts=st.rec_ts.at[b, v].set(fresh),
            rec_cnt=st.rec_cnt.at[b, v].set(1),
            rec_age=st.rec_age.at[b, v].set(ts),
            rec_loc=st.rec_loc.at[b, v].set(0),
        )
        if cfg.min_support == 1:
            st = _migrate_ref(cfg, st, block, b, v, st.rec_ts[b, v])
        return st

    def case_rec(st):
        cnt = st.rec_cnt[b, way]
        rec_ts = st.rec_ts.at[b, way, cnt].set(ts)
        st = st._replace(rec_ts=rec_ts, rec_cnt=st.rec_cnt.at[b, way].add(1))
        return lax.cond(
            st.rec_cnt[b, way] >= cfg.min_support,
            lambda s: _migrate_ref(cfg, s, block, b, way, s.rec_ts[b, way]),
            lambda s: s, st)

    def case_mine(st):
        row = st.rec_row[b, way]
        mcnt = st.mine_cnt[row]
        can = mcnt < cfg.max_support
        pos = jnp.minimum(mcnt, cfg.max_support - 1)
        mine_ts = st.mine_ts.at[pos, row].set(
            jnp.where(can, ts, st.mine_ts[pos, row]))
        mine_cnt = st.mine_cnt.at[row].set(
            jnp.where(can, mcnt + 1, cfg.max_support + 1))
        return st._replace(mine_ts=mine_ts, mine_cnt=mine_cnt)

    branch = jnp.where(found, jnp.where(in_mine, 2, 1), 0)
    state = lax.switch(branch, [case_new, case_rec, case_mine], state)
    return state._replace(ts=ts + 1)


# ---------------------------------------------------------------------------
# Frozen reference: pre-scatter add_association (lax.cond form, PR 2)
# ---------------------------------------------------------------------------

def add_association_reference(cfg, state, src, dst, valid):
    def do_add(st):
        b, way, found = probe(st.pf_key, src, cfg.pf_buckets)

        def update_existing(s):
            already = jnp.any(s.pf_vals[b, way] == dst)
            pos = jnp.mod(s.pf_cnt[b, way], cfg.prefetch_list)
            vals = s.pf_vals.at[b, way, pos].set(
                jnp.where(already, s.pf_vals[b, way, pos], dst))
            cnt = s.pf_cnt.at[b, way].add(jnp.where(already, 0, 1))
            age = s.pf_age.at[b, way].set(s.ts)
            return s._replace(pf_vals=vals, pf_cnt=cnt, pf_age=age,
                              n_pairs=s.n_pairs + jnp.where(already, 0, 1))

        def insert_new(s):
            v = choose_victim(s.pf_key[b], s.pf_age[b])
            fresh = jnp.full((cfg.prefetch_list,), EMPTY, jnp.int32).at[0].set(dst)
            return s._replace(
                pf_key=s.pf_key.at[b, v].set(src),
                pf_vals=s.pf_vals.at[b, v].set(fresh),
                pf_cnt=s.pf_cnt.at[b, v].set(1),
                pf_age=s.pf_age.at[b, v].set(s.ts),
                n_pairs=s.n_pairs + 1,
            )

        return lax.cond(found, update_existing, insert_new, st)

    return lax.cond(valid, do_add, lambda st: st, state)


# ---------------------------------------------------------------------------
# Frozen reference: pre-scatter pg_access (lax.cond form, PR 2)
# ---------------------------------------------------------------------------

def _upsert_node_ref(cfg, st, node):
    b, way, found = probe(st.key, node, cfg.buckets)

    def create(s):
        v = choose_victim(s.key[b], s.age[b])
        s = s._replace(
            key=s.key.at[b, v].set(node),
            nbr=s.nbr.at[b, v].set(
                jnp.full((cfg.out_degree,), EMPTY, jnp.int32)),
            cnt=s.cnt.at[b, v].set(jnp.zeros((cfg.out_degree,), jnp.int32)),
            occ=s.occ.at[b, v].set(0),
            age=s.age.at[b, v].set(s.clock))
        return s, v

    st, way = lax.cond(found, lambda s: (s, way), create, st)
    return st, b, way


def _add_edge_ref(cfg, st, src, dst):
    def upd(s):
        s, b, w = _upsert_node_ref(cfg, s, src)
        slots = s.nbr[b, w]
        hit = slots == dst
        have = jnp.any(hit)
        k_hit = jnp.argmax(hit).astype(jnp.int32)
        k_new = jnp.argmin(s.cnt[b, w]).astype(jnp.int32)
        k = jnp.where(have, k_hit, k_new)
        return s._replace(
            nbr=s.nbr.at[b, w, k].set(dst),
            cnt=s.cnt.at[b, w, k].set(jnp.where(have, s.cnt[b, w, k] + 1, 1)))

    return lax.cond((src != EMPTY) & (src != dst), upd, lambda s: s, st)


def pg_access_reference(cfg: PgConfig, st: PgState, block: jax.Array):
    st = st._replace(clock=st.clock + 1)
    for i in range(cfg.window):
        st = _add_edge_ref(cfg, st, st.hist[i], block)
    st, b, w = _upsert_node_ref(cfg, st, block)
    st = st._replace(occ=st.occ.at[b, w].add(1),
                     age=st.age.at[b, w].set(st.clock))

    counts, nbrs = st.cnt[b, w], st.nbr[b, w]
    occ = jnp.maximum(st.occ[b, w], 1)
    qual = (nbrs != EMPTY) & (counts * cfg.min_chance_den
                              >= occ * cfg.min_chance_num)
    score = jnp.where(qual, counts, -1)
    cands = []
    for _ in range(cfg.max_prefetch):
        k = jnp.argmax(score)
        ok = score[k] > 0
        cands.append(jnp.where(ok, nbrs[k], EMPTY))
        score = score.at[k].set(-1)
    out = jnp.stack(cands)

    hist = jnp.concatenate([st.hist[1:], block[None]])
    return st._replace(hist=hist), out


# ---------------------------------------------------------------------------
# Frozen reference: pre-scatter cache access / insert (lax.cond form, PR 2)
# ---------------------------------------------------------------------------

def _victim_with_second_chance_ref(state: CacheState, b):
    stamps = state.stamp[b]
    protected = (state.pf_flag[b] == 1) & (state.pf_sc[b] == 0)
    v0 = jnp.argmin(stamps).astype(jnp.int32)
    grant = protected[v0]
    new_stamp = state.stamp.at[b, v0].set(
        jnp.where(grant, state.clock, stamps[v0]))
    new_sc = state.pf_sc.at[b, v0].set(
        jnp.where(grant, 1, state.pf_sc[b, v0]))
    st = state._replace(stamp=new_stamp, pf_sc=new_sc)
    v1 = jnp.argmin(st.stamp[b]).astype(jnp.int32)
    victim = jnp.where(grant, v1, v0)
    return st, victim


def _insert_ref(state: CacheState, block, pf, src):
    from repro.core.hashindex import bucket_of
    b = bucket_of(block, state.key.shape[0])
    empty = state.key[b] == EMPTY
    any_empty = jnp.any(empty)

    def empty_path(st):
        return st, jnp.argmax(empty).astype(jnp.int32)

    st, way = jax.lax.cond(any_empty, empty_path,
                           lambda s: _victim_with_second_chance_ref(s, b),
                           state)
    ev = Evicted(
        block=jnp.where(any_empty, EMPTY, st.key[b, way]),
        unused_pf=(~any_empty) & (st.pf_flag[b, way] == 1),
        pf_src=jnp.where(any_empty, base.PF_NONE, st.pf_src[b, way]))
    st = st._replace(
        key=st.key.at[b, way].set(block),
        stamp=st.stamp.at[b, way].set(st.clock),
        pf_flag=st.pf_flag.at[b, way].set(pf),
        pf_sc=st.pf_sc.at[b, way].set(0),
        pf_src=st.pf_src.at[b, way].set(src),
        # learned-feature tables (ISSUE 8): maintained for every policy
        freq=st.freq.at[b, way].set(1),
        assoc=st.assoc.at[b, way].set(0))
    return st, ev


def _no_evict_ref():
    return Evicted(EMPTY, jnp.array(False), jnp.int32(base.PF_NONE))


def cache_access_reference(state: CacheState, block, policy="lru"):
    from repro.core.hashindex import bucket_of
    state = state._replace(clock=state.clock + 1)
    b = bucket_of(block, state.key.shape[0])
    ways_hit = state.key[b] == block
    hit = jnp.any(ways_hit)
    way = jnp.argmax(ways_hit).astype(jnp.int32)
    used_src = jnp.where(hit & (state.pf_flag[b, way] == 1),
                         state.pf_src[b, way], base.PF_NONE)

    def on_hit(st):
        stamp = (st.stamp.at[b, way].set(st.clock) if policy == "lru"
                 else st.stamp)
        st = st._replace(stamp=stamp,
                         pf_flag=st.pf_flag.at[b, way].set(0),
                         pf_src=st.pf_src.at[b, way].set(base.PF_NONE),
                         freq=st.freq.at[b, way].add(1))
        return st, _no_evict_ref()

    def on_miss(st):
        return _insert_ref(st, block, jnp.int32(0), jnp.int32(base.PF_NONE))

    state, ev = jax.lax.cond(hit, on_hit, on_miss, state)
    return state, hit, used_src, ev


def insert_prefetch_reference(state: CacheState, block, src, enable):
    do = enable & (block != EMPTY) & ~base.contains(state, block)
    state, ev = jax.lax.cond(
        do, lambda st: _insert_ref(st, block, jnp.int32(1), src),
        lambda st: (st, _no_evict_ref()), state)
    return state, do, ev


# ---------------------------------------------------------------------------
# Property tests
# ---------------------------------------------------------------------------

# small block universe so probes collide, victims evict, tables refill
BLOCKS = st.lists(st.integers(0, 40), min_size=1, max_size=100)

_CFGS = {name: small_cfg(min_support=r) for name, r in
         [("r2", 2), ("r1", 1)]}
_STEPS = {name: (jax.jit(functools.partial(record_event, cfg)),
                 jax.jit(functools.partial(record_event_reference, cfg)))
          for name, cfg in _CFGS.items()}


@settings(max_examples=20, deadline=None)
@given(BLOCKS)
def test_record_event_matches_reference(blocks):
    """Per-event bit-equivalence, incl. min_support==1 immediate migrate.

    The mining table is drained out-of-band (cleared, like ``mine`` does)
    whenever it fills, so the record-path invariant ``mine_fill <
    mine_rows`` holds without involving the mining procedure itself.
    """
    for name, cfg in _CFGS.items():
        step, step_ref = _STEPS[name]
        got, want = init(cfg), init(cfg)
        for blk in blocks:
            got = step(got, jnp.int32(blk))
            want = step_ref(want, jnp.int32(blk))
            assert_trees_equal(got, want, f"cfg={name} after block {blk}")
            if int(want.mine_fill) >= cfg.mine_rows:
                drained = want._replace(
                    rec_key=jnp.where(want.rec_loc == 1, EMPTY, want.rec_key),
                    rec_loc=jnp.zeros_like(want.rec_loc),
                    mine_block=jnp.full_like(want.mine_block, EMPTY),
                    mine_ts=jnp.zeros_like(want.mine_ts),
                    mine_cnt=jnp.zeros_like(want.mine_cnt),
                    mine_fill=jnp.zeros_like(want.mine_fill))
                got, want = drained, drained


@settings(max_examples=20, deadline=None)
@given(BLOCKS)
def test_record_event_disabled_is_noop(blocks):
    cfg = _CFGS["r2"]
    step = _STEPS["r2"][0]
    dis = jax.jit(functools.partial(record_event, cfg, enabled=False))
    stt = init(cfg)
    for blk in blocks:
        stt = step(stt, jnp.int32(blk))
        assert_trees_equal(dis(stt, jnp.int32(blk)), stt,
                           f"enabled=False mutated state on block {blk}")


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 2000), min_size=2, max_size=60))
def test_add_association_matches_reference(raw):
    cfg = small_cfg()
    got = want = init(cfg)._replace(ts=jnp.int32(7))
    add = jax.jit(functools.partial(add_association, cfg))
    add_ref = jax.jit(functools.partial(add_association_reference, cfg))
    for i in range(len(raw) - 1):
        src, dst = raw[i] % 50, raw[i + 1] % 50
        valid = jnp.array(raw[i] % 5 != 0)   # mix of masked-off pairs
        got = add(got, jnp.int32(src), jnp.int32(dst), valid)
        want = add_ref(want, jnp.int32(src), jnp.int32(dst), valid)
        assert_trees_equal(got, want, f"pair {i} ({src}->{dst}, v={valid})")


@settings(max_examples=20, deadline=None)
@given(BLOCKS)
def test_pg_access_matches_reference(blocks):
    cfg = PgConfig(buckets=16, ways=2, out_degree=3, max_prefetch=2)
    got, want = init_pg(cfg), init_pg(cfg)
    step = jax.jit(functools.partial(pg_access, cfg))
    step_ref = jax.jit(functools.partial(pg_access_reference, cfg))
    for blk in blocks:
        got, got_c = step(got, jnp.int32(blk))
        want, want_c = step_ref(want, jnp.int32(blk))
        assert_trees_equal(got, want, f"pg state after block {blk}")
        np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c),
                                      err_msg=f"pg cands on block {blk}")


@settings(max_examples=20, deadline=None)
@given(BLOCKS)
def test_pg_access_disabled_is_noop(blocks):
    cfg = PgConfig(buckets=16, ways=2, out_degree=3, max_prefetch=2)
    stt = init_pg(cfg)
    step = jax.jit(functools.partial(pg_access, cfg))
    dis = jax.jit(functools.partial(pg_access, cfg, enabled=False))
    for blk in blocks:
        stt, _ = step(stt, jnp.int32(blk))
        frozen, _ = dis(stt, jnp.int32(blk))
        assert_trees_equal(frozen, stt,
                           f"enabled=False mutated pg state on block {blk}")


_CACHE_STEPS = {
    policy: (jax.jit(functools.partial(base.access, policy=policy)),
             jax.jit(functools.partial(cache_access_reference,
                                       policy=policy)))
    for policy in ("lru", "fifo")
}
_PF_INS = jax.jit(base.insert_prefetch)
_PF_INS_REF = jax.jit(insert_prefetch_reference)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 12), min_size=1, max_size=80))
def test_cache_access_matches_reference(blocks):
    """Demand accesses + interleaved prefetch inserts on a tiny cache so
    evictions (and the second-chance refresh) trigger constantly."""
    for policy, (acc, acc_ref) in _CACHE_STEPS.items():
        got = want = base.init_cache(capacity=8, ways=2)
        for i, blk in enumerate(blocks):
            got, g_hit, g_src, g_ev, _ = acc(got, jnp.int32(blk))
            want, w_hit, w_src, w_ev = acc_ref(want, jnp.int32(blk))
            assert_trees_equal((got, g_hit, g_src, g_ev),
                               (want, w_hit, w_src, w_ev),
                               f"{policy}: access {i} (block {blk})")
            if i % 3 == 0:   # prefetch the successor, like a prefetcher
                src = jnp.int32(1 + i % 3)
                en = jnp.array(blk % 4 != 1)     # mix of suppressed inserts
                got, g_do, g_ev = _PF_INS(got, jnp.int32(blk + 1), src, en)
                want, w_do, w_ev = _PF_INS_REF(want, jnp.int32(blk + 1),
                                               src, en)
                assert_trees_equal((got, g_do, g_ev), (want, w_do, w_ev),
                                   f"{policy}: prefetch-insert {i}")


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 12), min_size=1, max_size=40))
def test_cache_access_disabled_is_noop(blocks):
    acc = _CACHE_STEPS["lru"][0]
    dis = jax.jit(functools.partial(base.access, enabled=False))
    stt = base.init_cache(capacity=8, ways=2)
    for blk in blocks:
        stt, _, _, _, _ = acc(stt, jnp.int32(blk))
        frozen, hit, used, ev, _ = dis(stt, jnp.int32(blk))
        assert_trees_equal(frozen, stt,
                           f"enabled=False mutated cache on block {blk}")
        assert not bool(hit) and int(used) == base.PF_NONE
        assert int(ev.block) == int(EMPTY)


_MINE_CFG = small_cfg(mine_rows=8, lookahead=12)
_MINE_STEP = jax.jit(functools.partial(record_event, _MINE_CFG))


def _mining_lanes(n_lanes: int, seed: int) -> list:
    """Lanes that recorded random blocks, each mined serially whenever
    its table filled, so each holds a part-full mining table."""
    cfg = _MINE_CFG
    rng = np.random.default_rng(seed)
    lanes = []
    for lane in range(n_lanes):
        stt = init(cfg)
        for blk in rng.integers(0, 30, size=60):
            stt = _MINE_STEP(stt, jnp.int32(blk))
            if int(stt.mine_fill) >= cfg.mine_rows:   # keep the invariant
                stt = mine(cfg, stt)
        lanes.append(stt)
    return lanes


def _assert_mine_batched_matches(lanes: list, need: np.ndarray,
                                 pairwise_fn=None) -> None:
    """Per-lane equality: mined lanes == mine(lane), others untouched."""
    cfg = _MINE_CFG
    states = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *lanes)
    got = mine_batched(cfg, states, jnp.asarray(need),
                       pairwise_fn=pairwise_fn)
    for i, lane in enumerate(lanes):
        want = mine(cfg, lane) if need[i] else lane
        got_i = jax.tree_util.tree_map(lambda x: x[i], got)
        assert_trees_equal(got_i, want, f"lane {i} (need={need[i]})")


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.integers(0, 7))
def test_mine_batched_matches_serial_mine(seed, need_bits):
    """Per-lane equality: mined lanes == mine(lane), others untouched."""
    need = np.array([bool(need_bits & (1 << i)) for i in range(3)])
    _assert_mine_batched_matches(_mining_lanes(3, seed), need)


@pytest.mark.parametrize("pairwise", ["oracle", "kernel"])
@pytest.mark.parametrize("need", [(1, 1, 1, 1, 1), (0, 1, 0, 1, 0),
                                  (0, 0, 0, 0, 0)],
                         ids=["every_lane", "two_apart", "no_lane"])
def test_mine_batched_matches_serial_mine_at_five_lanes(need, pairwise):
    """At 5 lanes: with every lane flagged, two flagged lanes that are
    not neighbours, or none, each flagged lane equals the serial
    ``mine`` bit for bit and every other lane is untouched. ``kernel``
    runs each pass's association search through the row-block Pallas
    kernel (interpreted here), as the sweep's barrier does on a TPU."""
    from repro.kernels.ops import mithril_pairwise
    _assert_mine_batched_matches(
        _mining_lanes(5, 2024), np.array(need, bool),
        mithril_pairwise if pairwise == "kernel" else None)


# ---------------------------------------------------------------------------
# Frozen reference: the row-gather lookup (before the per-slot read)
# ---------------------------------------------------------------------------

def lookup_reference(cfg: MithrilConfig, state: MithrilState,
                     block: jax.Array) -> jax.Array:
    b, way, found = probe(state.pf_key, block, cfg.pf_buckets)
    vals = state.pf_vals[b, way]
    return jnp.where(found, vals, jnp.full((cfg.prefetch_list,), EMPTY,
                                           jnp.int32))


def _prefetch_lane(cfg, rng):
    """A lane whose prefetch table holds random sources, each in its own
    hash bucket, with 1..P associations (EMPTY past them); free rows
    hold junk a miss must not return. Bucket 0 is full. Returns the
    state and, per case, a query block for it."""
    nb, w, p = cfg.pf_buckets, cfg.pf_ways, cfg.prefetch_list
    universe = np.arange(1, 4000, dtype=np.int32)
    owner = np.asarray(bucket_of(jnp.asarray(universe), nb))
    key = np.full((nb, w), -1, np.int32)
    vals = rng.integers(0, 1 << 20, size=(nb, w, p)).astype(np.int32)
    n_vals = np.zeros((nb, w), np.int64)
    for b in range(nb):
        here = rng.permutation(universe[owner == b])
        n_keys = w if b == 0 else int(rng.integers(0, w))
        ways = rng.permutation(w)[:n_keys]
        key[b, ways] = here[:n_keys]
        for way in ways:
            n_vals[b, way] = rng.integers(1, p + 1)
            vals[b, way, n_vals[b, way]:] = -1
    occupied = np.argwhere(key != -1)
    whole = [tuple(bw) for bw in occupied if n_vals[tuple(bw)] == p]
    holes = [tuple(bw) for bw in occupied if n_vals[tuple(bw)] < p]
    stored = set(key[key != -1].tolist())
    absent = [int(k) for k in universe
              if k not in stored and (key[owner[k - 1]] == -1).any()]
    outside = [int(k) for k in universe[owner == 0] if k not in stored]

    def pick(xs):
        return xs[int(rng.integers(len(xs)))]

    queries = {
        "found": int(key[pick(whole)]),
        "empty_slots": int(key[pick(holes)]),
        "absent": pick(absent),
        "full_bucket": pick([int(k) for k in key[0]] + outside[:w]),
    }
    state = init(cfg)._replace(pf_key=jnp.asarray(key),
                               pf_vals=jnp.asarray(vals))
    return state, queries


@pytest.mark.parametrize("case", ["found", "empty_slots", "absent",
                                  "full_bucket"])
@pytest.mark.parametrize("p", [2, 3], ids=["paper_p2", "suite_p3"])
def test_lookup_matches_row_gather(p, case):
    """``lookup`` reads each slot with its own gather; it returns what
    the row gather ``pf_vals[b, way]`` did, masked to EMPTY on a miss,
    for one lane and under ``vmap`` over 16 lanes (the sweep's form):
    a source with all P slots, one with EMPTY slots, an absent block,
    and a query into a full bucket (present or not)."""
    cfg = small_cfg(pf_buckets=8, pf_ways=4, prefetch_list=p)
    rng = np.random.default_rng(1000 * p + len(case))
    lanes = [_prefetch_lane(cfg, rng) for _ in range(16)]
    states = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                    *[s for s, _ in lanes])
    blocks = jnp.asarray([q[case] for _, q in lanes], jnp.int32)

    got = jax.jit(jax.vmap(functools.partial(lookup, cfg)))(states, blocks)
    want = jax.vmap(functools.partial(lookup_reference, cfg))(states, blocks)
    assert got.dtype == want.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for i, (stt, q) in enumerate(lanes):
        one = lookup(cfg, stt, jnp.int32(q[case]))
        np.testing.assert_array_equal(np.asarray(one), np.asarray(want[i]))
    hit = np.asarray(want != EMPTY).any(axis=1)
    if case in ("found", "empty_slots"):
        assert hit.all()
    if case == "absent":
        assert not hit.any()
    if case == "empty_slots":
        assert (np.asarray(want) == EMPTY).any(axis=1).all()
