"""Trace-driven cache+prefetch simulator (lax.scan over the request stream).

Composable the way the paper composes layers (Fig. 1): a replacement
policy (LRU/FIFO) underneath, any subset of {MITHRIL, AMP, PG} prefetching
on top — MITHRIL-AMP etc. fall out of the composition. One compiled scan
step per configuration; statistics match the paper's metrics:

  hit ratio            = hits / requests
  prefetch precision   = used prefetches / issued prefetches (per source)

A write-back cache (``SimConfig.write_back``, DESIGN.md §14) takes a
per-request write flag beside each block (``writes=``) and counts
writes, write hits, write-backs and the dirty blocks resident: a
running count, +1 when a write turns a clean block dirty and -1 at each
write-back, so its value at the end of a volume is the volume's flush.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import MithrilConfig, mithril
from repro.core.hashindex import EMPTY
from repro.learn.policy import LearnedConfig, make_scorer
from . import base
from .amp import AmpConfig, amp_access, amp_feedback_evicted, amp_feedback_used, init_amp
from .base import PF_AMP, PF_MITHRIL, PF_NONE, PF_PG, N_PF_SRC
from .pg import PgConfig, init_pg, pg_access


@dataclasses.dataclass(frozen=True)
class SimConfig:
    capacity: int = 4096          # cache capacity in blocks
    ways: int = 16
    policy: str = "lru"           # lru | fifo
    use_mithril: bool = False
    use_amp: bool = False
    use_pg: bool = False
    use_learned: bool = False     # learned admission/eviction (DESIGN.md §12)
    write_back: bool = False      # dirty state and write-back counts (§14)
    mithril: MithrilConfig = dataclasses.field(default_factory=MithrilConfig)
    amp: AmpConfig = dataclasses.field(default_factory=AmpConfig)
    pg: PgConfig = dataclasses.field(default_factory=PgConfig)
    learned: LearnedConfig = dataclasses.field(default_factory=LearnedConfig)

    def label(self) -> str:
        """Canonical config name: prefetchers joined by ``-``, then policy.

        Single source of truth for benchmark CSV columns and
        ``BENCH_sweep.json`` keys (e.g. ``mithril-amp-lru``,
        ``learned-mithril-lru``) — keep ``benchmarks.common.configs()``
        keyed off this.
        """
        parts = [n for n, u in [("learned", self.use_learned),
                                ("mithril", self.use_mithril),
                                ("amp", self.use_amp),
                                ("pg", self.use_pg),
                                ("wb", self.write_back)] if u]
        return "-".join(parts + [self.policy])


class Stats(NamedTuple):
    requests: jax.Array           # ()
    hits: jax.Array               # ()
    pf_issued: jax.Array          # (N_PF_SRC,)
    pf_used: jax.Array            # (N_PF_SRC,)
    pf_evicted_unused: jax.Array  # (N_PF_SRC,)
    # write-back caches only; None (no leaf) in a read-only one
    writes: Optional[jax.Array] = None          # ()
    write_hits: Optional[jax.Array] = None      # ()
    writebacks: Optional[jax.Array] = None      # () dirty evictions
    dirty_resident: Optional[jax.Array] = None  # () the flush still owed


WRITE_BACK_FIELDS = ("writes", "write_hits", "writebacks", "dirty_resident")


def init_stats(write_back: bool = False) -> Stats:
    z = jnp.zeros((), jnp.int32)
    zv = jnp.zeros((N_PF_SRC,), jnp.int32)
    stats = Stats(z, z, zv, zv.copy(), zv.copy())
    if write_back:
        stats = stats._replace(**dict.fromkeys(WRITE_BACK_FIELDS, z))
    return stats


def _count_writebacks(stats: Stats, ev) -> Stats:
    """Count the write-back a dirty victim owes (write-back caches)."""
    if ev.dirty is None:
        return stats
    with jax.named_scope("writeback"):
        d = ev.dirty.astype(jnp.int32)
        return stats._replace(writebacks=stats.writebacks + d,
                              dirty_resident=stats.dirty_resident - d)


class SimResult(NamedTuple):
    stats: Stats
    hit_curve: np.ndarray   # per-request hit boolean

    @property
    def hit_ratio(self) -> float:
        return float(self.stats.hits) / max(1, int(self.stats.requests))

    def precision(self, src: int) -> float:
        issued = int(self.stats.pf_issued[src])
        return float(self.stats.pf_used[src]) / issued if issued else float("nan")


def _apply_prefetches(cfg, cache, stats, cands, src, enable, scorer=None):
    """Insert a fixed-length candidate vector; collect eviction feedback."""
    ev_blocks, ev_unused, ev_srcs = [], [], []
    for i in range(cands.shape[0]):
        cache, issued, ev = base.insert_prefetch(
            cache, cands[i], jnp.int32(src), enable, scorer=scorer)
        stats = stats._replace(
            pf_issued=stats.pf_issued.at[src].add(issued.astype(jnp.int32)),
            pf_evicted_unused=stats.pf_evicted_unused.at[ev.pf_src].add(
                ev.unused_pf.astype(jnp.int32)))
        stats = _count_writebacks(stats, ev)
        ev_blocks.append(ev.block)
        ev_unused.append(ev.unused_pf)
        ev_srcs.append(ev.pf_src)
    return cache, stats, (jnp.stack(ev_blocks), jnp.stack(ev_unused),
                          jnp.stack(ev_srcs))


def build_segments(cfg: SimConfig):
    """Per-lane step split into segments separated by mining barriers.

    Returns ``(init_carry, segments)`` where ``segments`` is a list of
    ``(fn, mine_after)`` pairs and each ``fn(carry, block, aux)`` returns
    ``(carry, aux)``. ``aux`` threads per-request values (``valid``,
    ``hit``, ``used_src``, the demand eviction, and in a write-back
    configuration the request's ``write`` flag) between segments.
    ``mine_after=True`` marks a point where a MITHRIL recording event may
    have filled the mining table, so the mining trigger —
    ``mithril.maybe_mine`` per lane in the serial ``build_step``, the
    batch-level barrier in ``sweep.py`` — MUST run before the next
    segment (the record/maybe_mine contract of ``core.mithril``).

    The split exists for the batched sweep engine (``sweep.py``): the
    segments are branchless scatter updates (DESIGN.md §7), safe to vmap
    with no whole-table copies, while the (rare, expensive) mining pass
    stays *between* segments where the batched step guards it with one
    batch-level ``lax.cond``. ``aux["valid"]`` gates every state write at
    source — an invalid (padded-tail) request is a bit-exact no-op — so
    neither step builder needs a carry-wide select. The serial
    ``build_step`` passes ``valid=True`` and is bit-identical to
    triggering mining inside ``record``.
    """
    rec_on = cfg.mithril.record_on
    # learned eviction (DESIGN.md §12): one pure scorer closure per
    # config, threaded into every insertion path. Python-level branch on
    # a static config flag — no lax.cond enters the request path.
    scorer = make_scorer(cfg.learned) if cfg.use_learned else None

    def init_carry():
        carry = {
            "cache": base.init_cache(cfg.capacity, cfg.ways,
                                     write_back=cfg.write_back),
            "stats": init_stats(cfg.write_back),
        }
        if cfg.use_mithril:
            carry["mith"] = mithril.init(cfg.mithril)
        if cfg.use_amp:
            carry["amp"] = init_amp(cfg.amp)
        if cfg.use_pg:
            carry["pg"] = init_pg(cfg.pg)
        return carry

    def seg_access(carry, block, aux):
        """Demand access + hit/eviction statistics."""
        valid = aux["valid"]
        cache, stats = carry["cache"], carry["stats"]
        stats = stats._replace(requests=stats.requests + valid.astype(jnp.int32))
        # association-count feature for learned insertion: how many
        # associations mining has recorded with this block as source
        # (a pure pf-table read, so no mining-barrier interaction)
        hint = (mithril.assoc_count(cfg.mithril, carry["mith"], block)
                if cfg.use_learned and cfg.use_mithril else None)
        wb = ({"write": aux["write"]} if cfg.write_back else {})
        cache, hit, used_src, ev, dirtied = base.access(
            cache, block, cfg.policy, enabled=valid, scorer=scorer,
            assoc_hint=hint, **wb)
        stats = stats._replace(
            hits=stats.hits + hit.astype(jnp.int32),
            pf_used=stats.pf_used.at[used_src].add(
                (used_src != PF_NONE).astype(jnp.int32)),
            pf_evicted_unused=stats.pf_evicted_unused.at[ev.pf_src].add(
                ev.unused_pf.astype(jnp.int32)))
        if cfg.write_back:
            with jax.named_scope("writeback"):
                write = aux["write"] & valid
                stats = stats._replace(
                    writes=stats.writes + write.astype(jnp.int32),
                    write_hits=stats.write_hits
                    + (write & hit).astype(jnp.int32),
                    dirty_resident=stats.dirty_resident
                    + dirtied.astype(jnp.int32))
            stats = _count_writebacks(stats, ev)
        out = dict(carry)
        out["cache"], out["stats"] = cache, stats
        return out, {**aux, "hit": hit, "used_src": used_src, "ev": ev}

    def seg_record_miss(carry, block, aux):
        # branchless gate: a disabled record event is a bit-exact no-op,
        # so no lax.cond (which vmap would lower to whole-table selects)
        mith = mithril.record_event(cfg.mithril, carry["mith"], block,
                                    enabled=aux["valid"] & ~aux["hit"])
        return {**carry, "mith": mith}, aux

    def seg_record_evict(carry, block, aux):
        ev = aux["ev"]
        mith = mithril.record_event(cfg.mithril, carry["mith"], ev.block,
                                    enabled=ev.block != EMPTY)
        return {**carry, "mith": mith}, aux

    def seg_record_all(carry, block, aux):
        mith = mithril.record_event(cfg.mithril, carry["mith"], block,
                                    enabled=aux["valid"])
        return {**carry, "mith": mith}, aux

    # ``record_gate`` marks a segment as a pure MITHRIL recording event
    # and exposes its (block, enabled) expressions in elementwise form.
    # The batched step builder (sweep.py) uses it to route the segment
    # through ``mithril.record_event_batched`` — the fused Pallas record
    # kernel on TPU, the identical vmapped scatter form elsewhere —
    # instead of vmapping the segment closure. The expressions MUST
    # mirror the segment bodies above; ``tests/test_record_kernel.py``
    # pins the two paths bit-identical.
    seg_record_miss.record_gate = \
        lambda block, aux: (block, aux["valid"] & ~aux["hit"])
    seg_record_evict.record_gate = \
        lambda block, aux: (aux["ev"].block, aux["ev"].block != EMPTY)
    seg_record_all.record_gate = lambda block, aux: (block, aux["valid"])

    # Prefetching, one segment per enabled layer (no mining in
    # these), in the order MITHRIL, AMP, PG: each inserts into the cache
    # the previous one left.
    def seg_prefetch(carry, block, aux):
        """MITHRIL prefetch-list check (Alg. 3 pFlag path)."""
        cands = mithril.lookup(cfg.mithril, carry["mith"], block)
        cache, stats, _ = _apply_prefetches(
            cfg, carry["cache"], carry["stats"], cands, PF_MITHRIL,
            aux["valid"], scorer=scorer)
        return {**carry, "cache": cache, "stats": stats}, aux

    def seg_amp(carry, block, aux):
        """AMP sequential prefetching + degree feedback. Every piece is
        source-gated: the feedbacks key off valid-gated signals
        (used_src / eviction records are inert on invalid requests) and
        amp_access takes `valid` directly, so no subtree select remains."""
        valid, ev = aux["valid"], aux["ev"]
        amp = amp_feedback_used(cfg.amp, carry["amp"], block,
                                aux["used_src"] == PF_AMP)
        amp, vec = amp_access(cfg.amp, amp, block, enabled=valid)
        cache, stats, evs = _apply_prefetches(
            cfg, carry["cache"], carry["stats"], vec, PF_AMP, valid,
            scorer=scorer)
        evb, evu, evsrc = evs
        for i in range(evb.shape[0]):
            amp = amp_feedback_evicted(cfg.amp, amp, evb[i],
                                       evu[i] & (evsrc[i] == PF_AMP))
        amp = amp_feedback_evicted(cfg.amp, amp, ev.block,
                                   ev.unused_pf & (ev.pf_src == PF_AMP))
        return {**carry, "cache": cache, "stats": stats, "amp": amp}, aux

    def seg_pg(carry, block, aux):
        """Probability-graph prefetching."""
        valid = aux["valid"]
        pg, cands = pg_access(cfg.pg, carry["pg"], block, enabled=valid)
        cache, stats, _ = _apply_prefetches(
            cfg, carry["cache"], carry["stats"], cands, PF_PG, valid,
            scorer=scorer)
        return {**carry, "cache": cache, "stats": stats, "pg": pg}, aux

    segments = [(seg_access, False)]
    if cfg.use_mithril:
        if rec_on in ("miss", "miss+evict"):
            segments.append((seg_record_miss, True))
        if rec_on in ("evict", "miss+evict"):
            segments.append((seg_record_evict, True))
        if rec_on == "all":
            segments.append((seg_record_all, True))
    segments += [(fn, False) for fn, on in ((seg_prefetch, cfg.use_mithril),
                                            (seg_amp, cfg.use_amp),
                                            (seg_pg, cfg.use_pg)) if on]
    return init_carry, segments


def build_step(cfg: SimConfig):
    """Returns (init_carry, step) for lax.scan over a block trace.

    Serial composition of ``build_segments`` with the per-lane
    ``mithril.maybe_mine`` trigger at every mining barrier — the
    record/maybe_mine contract in its one-lane form. A write-back
    configuration scans ``(block, write)`` pairs.
    """
    init_carry, segments = build_segments(cfg)

    def step(carry, xs):
        block, write = xs if cfg.write_back else (xs, None)
        aux = {"valid": jnp.array(True)}
        if cfg.write_back:
            aux["write"] = write
        for fn, mine_after in segments:
            carry, aux = fn(carry, block, aux)
            if mine_after:
                carry = {**carry,
                         "mith": mithril.maybe_mine(cfg.mithril,
                                                    carry["mith"])}
        return carry, aux["hit"]

    return init_carry, step


def write_flags(cfg: SimConfig, writes, n: int) -> np.ndarray:
    """``writes`` as ``n`` request write flags: all reads where ``None``.
    Only a write-back configuration takes write flags."""
    if writes is None:
        return np.zeros((n,), bool)
    if not cfg.write_back:
        raise ValueError("writes= needs a write-back configuration "
                         "(SimConfig(write_back=True))")
    writes = np.asarray(writes)
    if writes.shape != (n,):
        raise ValueError(f"writes must have shape ({n},), "
                         f"got {writes.shape}")
    return writes.astype(bool)


def simulate(cfg: SimConfig, trace: np.ndarray,
             unroll: int = 1, writes=None) -> SimResult:
    """Run ``trace`` (1-D int array of block ids) through the configuration;
    ``writes`` gives a write-back configuration each request's write flag
    (default all reads)."""
    init_carry, step = build_step(cfg)
    flags = write_flags(cfg, writes, len(trace))

    @jax.jit
    def run(xs):
        carry, hits = lax.scan(step, init_carry(), xs, unroll=unroll)
        return carry["stats"], hits

    xs = jnp.asarray(trace, jnp.int32)
    if cfg.write_back:
        xs = (xs, jnp.asarray(flags))
    stats, hits = run(xs)
    return SimResult(jax.device_get(stats), np.asarray(hits))


def max_hit_ratio(trace: np.ndarray) -> float:
    """1 - cold-miss ratio: the paper's 'maximum obtainable hit ratio'."""
    n_unique = len(np.unique(trace))
    return 1.0 - n_unique / max(1, len(trace))


class SimSession:
    """Incremental simulation: feed requests as they arrive (§10).

    The scan-based drivers (``simulate``, the sweep engines) want the
    whole trace up front; a serving integration has requests *arriving*.
    A session holds the carry between calls and steps the compiled chunk
    runner (``sweep._runner`` at lane width 1 — shared executable cache,
    so sessions cost no extra compiles beyond the first per (config,
    chunk)) whenever a full chunk of requests has accumulated; the
    remainder is flushed masked at :meth:`finish`. Statistics and hit
    curve are bit-identical to ``simulate`` on the concatenated feed
    regardless of how the feed was sliced — the chunk boundary is
    invisible under the §6 masking contract
    (``tests/test_streaming.py`` pins this). A write-back configuration
    takes each fed request's write flag (``feed(blocks, writes=)``).
    """

    def __init__(self, cfg: SimConfig, chunk: int = 256, unroll: int = 1):
        from .sweep import _runner   # deferred: sweep imports this module
        if chunk < 1:
            raise ValueError(f"chunk must be >= 1, got {chunk}")
        init_batched, self._run, place = _runner(cfg, unroll, 1)
        self._cfg = cfg
        self._carry = place(init_batched(1))
        self._chunk = int(chunk)
        self._pending = np.empty((0,), np.int32)
        self._pending_w = np.empty((0,), bool)
        self._hits: list = []
        self._fed = 0
        self._done = False

    @property
    def requests_fed(self) -> int:
        return self._fed

    def _run_chunk(self, blk: np.ndarray, valid: np.ndarray,
                   wr: np.ndarray) -> None:
        slabs = [jnp.asarray(blk[:, None]), jnp.asarray(valid[:, None])]
        if self._cfg.write_back:
            slabs.append(jnp.asarray(wr[:, None]))
        self._carry, hits = self._run(self._carry, *slabs)
        self._hits.append(hits)

    def feed(self, blocks, writes=None) -> None:
        """Append arrived requests; full chunks run immediately."""
        if self._done:
            raise RuntimeError("session already finished")
        blocks = np.atleast_1d(np.asarray(blocks, np.int32))
        writes = write_flags(self._cfg, None if writes is None
                             else np.atleast_1d(writes), len(blocks))
        self._fed += len(blocks)
        self._pending = np.concatenate([self._pending, blocks])
        self._pending_w = np.concatenate([self._pending_w, writes])
        while len(self._pending) >= self._chunk:
            blk = self._pending[: self._chunk]
            wr = self._pending_w[: self._chunk]
            self._pending = self._pending[self._chunk:]
            self._pending_w = self._pending_w[self._chunk:]
            self._run_chunk(blk, np.ones((self._chunk,), bool), wr)

    def finish(self) -> SimResult:
        """Flush the padded remainder and return the SimResult."""
        if self._done:
            raise RuntimeError("session already finished")
        self._done = True
        if len(self._pending):
            blk = np.zeros((self._chunk,), np.int32)
            blk[: len(self._pending)] = self._pending
            wr = np.zeros((self._chunk,), bool)
            wr[: len(self._pending)] = self._pending_w
            valid = np.arange(self._chunk) < len(self._pending)
            self._run_chunk(blk, valid, wr)
        stats = jax.tree.map(lambda leaf: np.asarray(leaf)[0],
                             self._carry["stats"])
        hits = (np.concatenate([np.asarray(h)[:, 0] for h in self._hits])
                if self._hits else np.zeros((0,), bool))
        return SimResult(stats, hits[: self._fed])
