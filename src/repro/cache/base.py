"""Fixed-capacity cache replacement policies as pure JAX functions.

Caches are set-associative (buckets x ways) so that every operation is a
bounded vector op under jit — the same structural choice real hardware
caches make. With >=16 ways the hit-ratio difference vs. a fully
associative LRU is small; `tests/test_cache.py` quantifies it against an
exact Python LRU oracle.

Policies: ``lru`` (stamp = last access) and ``fifo`` (stamp = insert time).
Prefetched blocks carry a flag for (a) precision accounting and (b) the
paper's second-chance rule: an unused prefetched block that would be
evicted is instead refreshed to MRU once (Sec. 4.2.2).

Learned eviction (DESIGN.md §12) plugs in through the optional ``scorer``
argument of :func:`access` / :func:`insert_prefetch`: a pure function of
the per-way feature rows (recency, frequency, association hint, prefetch
flag) returning a keep-score per way. When given, the victim is the
minimum-score way instead of the minimum-stamp way; everything else —
second chance, the one-row-write-per-table scatter form, the
``enabled=False`` bit-exact no-op — is unchanged. The feature tables
(``freq``, ``assoc``) are maintained for every policy so that switching
the scorer on never changes the carry structure.

A write-back cache (``init_cache(..., write_back=True)``, DESIGN.md §14)
adds one table, ``dirty``: a write sets its way's bit, on a hit or as
the fill of a write miss (write-allocate); a prefetched block enters
clean; every eviction, demand or prefetch, reports in
``Evicted.dirty`` whether it pushed a dirty block out, which the caller
counts as a write-back. A read-only cache has no dirty table and no
``Evicted.dirty`` (both ``None``: no carry leaf, no operation), so its
compiled step is the one it was before write-back existed. The dirty
bookkeeping runs under ``jax.named_scope("writeback")``.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.hashindex import EMPTY, bucket_of

# prefetcher ids for per-source precision accounting
PF_NONE, PF_MITHRIL, PF_AMP, PF_PG = 0, 1, 2, 3
N_PF_SRC = 4


class CacheState(NamedTuple):
    key: jax.Array      # (NB, W) int32 block id or EMPTY
    stamp: jax.Array    # (NB, W) int32 recency (lru) / insertion (fifo) stamp
    pf_flag: jax.Array  # (NB, W) int32 1 = prefetched & not yet used
    pf_sc: jax.Array    # (NB, W) int32 1 = second chance consumed
    pf_src: jax.Array   # (NB, W) int32 which prefetcher inserted it
    freq: jax.Array     # (NB, W) int32 accesses while resident (learned feat.)
    assoc: jax.Array    # (NB, W) int32 association-count hint at insert time
    clock: jax.Array    # () int32
    # (NB, W) int32 1 = written since it was filled; write-back only
    dirty: Optional[jax.Array] = None


class Evicted(NamedTuple):
    block: jax.Array      # () int32 or EMPTY
    unused_pf: jax.Array  # () bool: was an unused prefetched block
    pf_src: jax.Array     # () int32
    dirty: Optional[jax.Array] = None   # () bool: a dirty block; write-back


def init_cache(capacity: int, ways: int = 16,
               write_back: bool = False) -> CacheState:
    """``capacity`` is rounded to a power-of-two bucket count x ways;
    ``write_back`` adds the dirty table."""
    nb = max(1, capacity // ways)
    nb = 1 << (nb - 1).bit_length() if nb & (nb - 1) else nb  # pow2 ceil
    shape = (nb, ways)
    i32 = jnp.int32
    return CacheState(
        key=jnp.full(shape, EMPTY, i32), stamp=jnp.zeros(shape, i32),
        pf_flag=jnp.zeros(shape, i32), pf_sc=jnp.zeros(shape, i32),
        pf_src=jnp.zeros(shape, i32), freq=jnp.zeros(shape, i32),
        assoc=jnp.zeros(shape, i32), clock=jnp.zeros((), i32),
        dirty=jnp.zeros(shape, i32) if write_back else None)


def _no_evict(state: CacheState) -> Evicted:
    return Evicted(EMPTY, jnp.array(False), jnp.int32(PF_NONE),
                   None if state.dirty is None else jnp.array(False))


def contains(state: CacheState, block: jax.Array) -> jax.Array:
    b = bucket_of(block, state.key.shape[0])
    return jnp.any(state.key[b] == block)


def _insert_rows(state: CacheState, b: jax.Array, block: jax.Array,
                 pf: jax.Array, src: jax.Array,
                 assoc_hint: jax.Array = None, scorer=None,
                 dirty_in: jax.Array = None):
    """Insertion as branchless row values for bucket ``b``.

    Returns ``(rows, ev)`` where ``rows`` are the post-insert
    (key, stamp, pf_flag, pf_sc, pf_src, freq, assoc) rows, and in a
    write-back cache the dirty row after them, the filled way holding
    ``dirty_in`` (default clean). The
    empty-way / second-chance / plain-eviction cases are all computed on
    the (W,) bucket rows and selected as scalars (DESIGN.md §7) — the
    caller applies one ``.at[b].set(row)`` scatter per table, so under
    ``vmap`` nothing ever copies the whole cache.

    ``scorer(recency, freq, assoc, pf_flag) -> (W,) scores`` replaces
    the minimum-stamp victim with the minimum-score way (learned
    eviction, DESIGN.md §12); ``scorer=None`` is the exact historical
    stamp rule. Both victim rules consult the same second-chance
    protection.
    """
    keys, stamps = state.key[b], state.stamp[b]
    flags, scs, srcs = state.pf_flag[b], state.pf_sc[b], state.pf_src[b]
    freqs, assocs = state.freq[b], state.assoc[b]
    if assoc_hint is None:
        assoc_hint = jnp.int32(0)
    ways = jnp.arange(keys.shape[0])

    empty = keys == EMPTY
    any_empty = jnp.any(empty)
    w_empty = jnp.argmax(empty).astype(jnp.int32)

    # second chance: only consulted (and consumed) when evicting. The
    # victim, if an unused prefetch with its chance left, is refreshed
    # to MRU once and the next-best victim evicts instead.
    protected = (flags == 1) & (scs == 0)
    if scorer is None:
        v0 = jnp.argmin(stamps).astype(jnp.int32)
        grant = protected[v0] & ~any_empty
        stamps = jnp.where((ways == v0) & grant, state.clock, stamps)
        scs = jnp.where((ways == v0) & grant, 1, scs)
        v1 = jnp.argmin(stamps).astype(jnp.int32)
    else:
        scores = scorer(state.clock - stamps, freqs, assocs, flags)
        v0 = jnp.argmin(scores).astype(jnp.int32)
        grant = protected[v0] & ~any_empty
        stamps = jnp.where((ways == v0) & grant, state.clock, stamps)
        scs = jnp.where((ways == v0) & grant, 1, scs)
        # a granted way is out of the running this insertion; the stamp
        # refresh above keeps the LRU bookkeeping consistent with it
        top = (jnp.iinfo(scores.dtype).max
               if jnp.issubdtype(scores.dtype, jnp.integer) else jnp.inf)
        scores = jnp.where((ways == v0) & grant, top, scores)
        v1 = jnp.argmin(scores).astype(jnp.int32)
    way = jnp.where(any_empty, w_empty, jnp.where(grant, v1, v0))

    ev = Evicted(
        block=jnp.where(any_empty, EMPTY, keys[way]),
        unused_pf=(~any_empty) & (flags[way] == 1),
        pf_src=jnp.where(any_empty, PF_NONE, srcs[way]))

    at = ways == way
    rows = (jnp.where(at, block, keys), jnp.where(at, state.clock, stamps),
            jnp.where(at, pf, flags), jnp.where(at, 0, scs),
            jnp.where(at, src, srcs), jnp.where(at, 1, freqs),
            jnp.where(at, assoc_hint, assocs))
    if state.dirty is not None:
        with jax.named_scope("writeback"):
            dirties = state.dirty[b]
            ev = ev._replace(dirty=(~any_empty) & (dirties[way] == 1))
            fill = jnp.int32(0) if dirty_in is None else dirty_in
            rows += (jnp.where(at, fill, dirties),)
    return rows, ev


def _masked_rows(state: CacheState, b: jax.Array, rows, do: jax.Array):
    """Select ``rows`` where ``do`` else the current bucket rows."""
    old = (state.key[b], state.stamp[b], state.pf_flag[b],
           state.pf_sc[b], state.pf_src[b], state.freq[b], state.assoc[b])
    out = tuple(jnp.where(do, new, o) for new, o in zip(rows, old))
    if state.dirty is not None:
        with jax.named_scope("writeback"):
            out += (jnp.where(do, rows[7], state.dirty[b]),)
    return out


def _set_bucket(state: CacheState, b: jax.Array, rows) -> CacheState:
    key, stamp, flag, sc, src, freq, assoc = rows[:7]
    state = state._replace(
        key=state.key.at[b].set(key), stamp=state.stamp.at[b].set(stamp),
        pf_flag=state.pf_flag.at[b].set(flag),
        pf_sc=state.pf_sc.at[b].set(sc), pf_src=state.pf_src.at[b].set(src),
        freq=state.freq.at[b].set(freq), assoc=state.assoc.at[b].set(assoc))
    if state.dirty is not None:
        with jax.named_scope("writeback"):
            state = state._replace(dirty=state.dirty.at[b].set(rows[7]))
    return state


def access(state: CacheState, block: jax.Array, policy: str = "lru",
           enabled: jax.Array = True, scorer=None,
           assoc_hint: jax.Array = None, write: jax.Array = None):
    """Demand access. Returns (state, hit, used_pf_src, evicted, dirtied).

    On miss the block is demand-inserted. ``used_pf_src`` is the
    prefetcher id if this hit consumed a prefetched block (else PF_NONE).
    Hit and miss both resolve to one row write per table in bucket ``b``.
    With ``enabled=False`` the access is a bit-exact no-op reporting
    ``(hit=False, PF_NONE, no-evict)`` — how the sweep engine freezes
    exhausted trace lanes without a carry-wide select. ``scorer`` /
    ``assoc_hint`` select learned eviction (see :func:`_insert_rows`);
    hits additionally bump the way's residency frequency.

    In a write-back cache ``write`` (default a read) marks the block
    dirty, on a hit or as the fill of a miss, and ``dirtied`` says
    whether the access turned a clean block dirty: the write-back that
    block now owes, at its eviction or at the end of the volume. A
    read-only cache takes no ``write`` and returns ``dirtied`` as
    ``None``.
    """
    if state.dirty is None and write is not None:
        raise ValueError("write= needs a write-back cache "
                         "(init_cache(..., write_back=True))")
    enabled = jnp.asarray(enabled)
    state = state._replace(clock=state.clock + enabled.astype(jnp.int32))
    b = bucket_of(block, state.key.shape[0])
    keys = state.key[b]
    ways_hit = keys == block
    hit = jnp.any(ways_hit)
    way = jnp.argmax(ways_hit).astype(jnp.int32)
    at = jnp.arange(keys.shape[0]) == way

    used_src = jnp.where(enabled & hit & (state.pf_flag[b, way] == 1),
                         state.pf_src[b, way], PF_NONE)

    # hit: touch the way (LRU), consume its prefetch flag, bump frequency
    hit_stamp = (jnp.where(at, state.clock, state.stamp[b])
                 if policy == "lru" else state.stamp[b])
    hit_rows = (keys, hit_stamp,
                jnp.where(at, 0, state.pf_flag[b]), state.pf_sc[b],
                jnp.where(at, PF_NONE, state.pf_src[b]),
                jnp.where(at, state.freq[b] + 1, state.freq[b]),
                state.assoc[b])
    fill = dirtied = None
    if state.dirty is not None:
        with jax.named_scope("writeback"):
            write = jnp.asarray(False if write is None else write, bool)
            fill = write.astype(jnp.int32)
            was_dirty = hit & (state.dirty[b, way] == 1)
            hit_rows += (jnp.where(at & write, 1, state.dirty[b]),)
            dirtied = enabled & write & ~was_dirty

    # miss: demand-insert
    ins_rows, ins_ev = _insert_rows(state, b, block, jnp.int32(0),
                                    jnp.int32(PF_NONE),
                                    assoc_hint=assoc_hint, scorer=scorer,
                                    dirty_in=fill)

    rows = tuple(jnp.where(hit, h, m) for h, m in zip(hit_rows, ins_rows))
    ev = jax.tree.map(lambda n, m: jnp.where(enabled & ~hit, m, n),
                      _no_evict(state), ins_ev)
    return (_set_bucket(state, b, _masked_rows(state, b, rows, enabled)),
            hit & enabled, used_src, ev, dirtied)


def insert_prefetch(state: CacheState, block: jax.Array, src: jax.Array,
                    enable: jax.Array, scorer=None,
                    assoc_hint: jax.Array = None):
    """Prefetch-insert ``block`` if enabled, valid and absent.

    Returns (state, issued, evicted). A suppressed insert writes the
    bucket rows back unchanged (bit-exact no-op, no ``lax.cond``). The
    block enters clean; in a write-back cache ``evicted.dirty`` says
    whether the insert pushed a dirty block out.
    """
    do = enable & (block != EMPTY) & ~contains(state, block)
    b = bucket_of(block, state.key.shape[0])
    rows, ins_ev = _insert_rows(state, b, block, jnp.int32(1), src,
                                assoc_hint=assoc_hint, scorer=scorer)
    ev = jax.tree.map(lambda i, n: jnp.where(do, i, n), ins_ev,
                      _no_evict(state))
    return _set_bucket(state, b, _masked_rows(state, b, rows, do)), do, ev
