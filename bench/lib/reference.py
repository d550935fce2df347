"""Plain reference of the simulated cache: LRU, AMP and MITHRIL, one volume
at a time, in pure Python.

It follows the semantics the configuration file states, request by
request, with no batching, no lanes and no JAX, and it imports nothing
of the program under test. The per-volume counts it returns are what
the benchmark's ``correct`` compares with the sweep engine's output.

Semantics (the configuration file names each size):

* cache: ``capacity`` blocks in ``capacity / ways`` buckets (rounded up
  to a power of two) of ``ways`` ways; a block's bucket is the low bits
  of a murmur3-style 32-bit finalizer of its id. LRU: a hit restamps the
  way with the request clock; a miss fills the first empty way, else
  evicts the way with the oldest stamp (lowest index on ties). A
  prefetched block not yet used gets one second chance: when it is the
  victim it is restamped to the current clock, and the next-oldest way
  is evicted instead;
* MITHRIL (Yang et al., SoCC'17, Alg. 1-3) records each demand miss. A
  ``rec_ways``-way recording table keeps up to ``min_support`` (R)
  timestamps per block (timestamps count recording events) and evicts
  FIFO by insertion time; at R timestamps the block migrates to the next
  free row of the mining table, which then collects its timestamps up to
  ``max_support`` (S; one more marks the block frequent). When the mining
  table is full, mining sorts its valid rows by first timestamp and
  pairs row i with each later row j whose first timestamps lie within
  ``lookahead`` (Delta): rows with the same count whose aligned
  timestamps all differ by at most Delta associate, strongly when one
  differs by exactly 1. Per source row the first association and every
  strong one are kept, in that discovery order, up to ``2 * mine_rows``
  pairs. Each pair lands in the ``pf_ways``-way prefetching table: a
  known source appends the destination FIFO into its P slots (unless it
  is there already), a new source takes the first empty way, else the
  way touched longest ago. Then the mining table and the recording
  entries that migrated into it are cleared. After the record step,
  the P associated blocks of the requested block are prefetched;
* AMP (Gill and Bathen, FAST'07) as the repository models it:
  ``n_streams`` stream slots; a request continues the first stream whose last block is the
  one before it, else takes the least recently used slot. After the
  second request of a run, when the block is within half the degree of
  the stream's prefetch frontier, it prefetches up to ``deg`` blocks past
  the larger of frontier and block (degrees start at ``init_degree``). A
  used prefetch from a stream grows its degree by one (to
  ``max_degree``); one evicted unused shrinks it (to 1).

Counts per volume: requests, hits, and per prefetch source (0 none,
1 MITHRIL, 2 AMP, 3 PG) prefetches issued, used, and evicted unused.
"""

from __future__ import annotations

from typing import List, NamedTuple

EMPTY = -1
INT32_MAX = 2**31 - 1
MASK32 = 0xFFFFFFFF
NONE, MITHRIL, AMP = 0, 1, 2
N_SRC = 4


def mix32(key: int) -> int:
    """Murmur3 finalizer on the 32-bit pattern of ``key``."""
    k = key & MASK32
    k ^= k >> 16
    k = (k * 0x7FEB352D) & MASK32
    k ^= k >> 15
    k = (k * 0x846CA68B) & MASK32
    k ^= k >> 16
    return k


def bucket_of(key: int, n_buckets: int) -> int:
    return mix32(key) & (n_buckets - 1)


def _argmin(xs: List[int]) -> int:
    return xs.index(min(xs))


class Counts(NamedTuple):
    requests: int
    hits: int
    pf_issued: tuple            # (4,) per prefetch source
    pf_used: tuple
    pf_evicted_unused: tuple

    def flat(self) -> List[int]:
        return [self.requests, self.hits, *self.pf_issued, *self.pf_used,
                *self.pf_evicted_unused]


class _Cache:
    def __init__(self, capacity: int, ways: int):
        nb = max(1, capacity // ways)
        if nb & (nb - 1):
            nb = 1 << (nb - 1).bit_length()
        self.nb, self.ways = nb, ways
        self.key = [[EMPTY] * ways for _ in range(nb)]
        self.stamp = [[0] * ways for _ in range(nb)]
        self.flag = [[0] * ways for _ in range(nb)]    # prefetched, unused
        self.sc = [[0] * ways for _ in range(nb)]      # second chance spent
        self.src = [[NONE] * ways for _ in range(nb)]
        self.clock = 0

    def bucket(self, block: int) -> int:
        return bucket_of(block, self.nb)

    def insert(self, block: int, pf: int, src: int):
        """Place ``block``; returns the evicted (block, unused, src)."""
        b = self.bucket(block)
        keys, stamps = self.key[b], self.stamp[b]
        flags, scs, srcs = self.flag[b], self.sc[b], self.src[b]
        if EMPTY in keys:
            way = keys.index(EMPTY)
            ev = (EMPTY, False, NONE)
        else:
            way = _argmin(stamps)
            if flags[way] == 1 and scs[way] == 0:     # second chance
                stamps[way] = self.clock
                scs[way] = 1
                way = _argmin(stamps)
            ev = (keys[way], flags[way] == 1, srcs[way])
        keys[way], stamps[way] = block, self.clock
        flags[way], scs[way], srcs[way] = pf, 0, src
        return ev

    def contains(self, block: int) -> bool:
        return block in self.key[self.bucket(block)]


class _Mithril:
    def __init__(self, c: dict, defer_mining: bool = False):
        self.defer_mining = defer_mining
        self.R, self.S = c["min_support"], c["max_support"]
        self.delta, self.P = c["lookahead"], c["prefetch_list"]
        self.nb, self.w = c["rec_buckets"], c["rec_ways"]
        self.nm = c["mine_rows"]
        self.pb, self.pw = c["pf_buckets"], c["pf_ways"]
        self.window = (min(c["max_window"], self.nm - 1) if c["max_window"]
                       else min(self.nm - 1, self.delta))
        self.pairs_cap = c["max_pairs"] or 2 * self.nm
        n = self.nb * self.w
        self.rec_key = [EMPTY] * n
        self.rec_ts: List[list] = [None] * n   # made when a block enters
        self.rec_cnt = [0] * n
        self.rec_age = [0] * n
        self.rec_loc = [0] * n      # 1: the block's row is in the mining table
        self.rec_row = [0] * n
        self.migrated: List[int] = []
        self.mine_block: List[int] = []     # mining-table rows, in fill order
        self.mine_ts: List[list] = []
        self.mine_cnt: List[int] = []
        m = self.pb * self.pw
        self.pf_key = [EMPTY] * m
        self.pf_vals: List[list] = [None] * m
        self.pf_cnt = [0] * m
        self.pf_age = [0] * m
        self.ts = 0

    @staticmethod
    def _locate(keys, ages, base: int, ways: int, key: int):
        """(slot, found): the key's slot, else the first empty way, else
        the way with the lowest age."""
        row = keys[base: base + ways]
        if key in row:
            return base + row.index(key), True
        if EMPTY in row:
            return base + row.index(EMPTY), False
        return base + _argmin(ages[base: base + ways]), False

    def record(self, block: int) -> None:
        ts, R, S = self.ts, self.R, self.S
        base = bucket_of(block, self.nb) * self.w
        i, found = self._locate(self.rec_key, self.rec_age, base, self.w,
                                block)
        if found and self.rec_loc[i] == 1:
            m = self.rec_row[i]
            c = self.mine_cnt[m]
            if c < S:
                self.mine_ts[m][c] = ts
                self.mine_cnt[m] = c + 1
            else:
                self.mine_cnt[m] = S + 1          # frequent: out of mining
        else:
            if found:
                self.rec_ts[i][self.rec_cnt[i]] = ts
                self.rec_cnt[i] += 1
            else:
                self.rec_key[i] = block
                self.rec_ts[i] = [ts] + [0] * (R - 1)
                self.rec_cnt[i] = 1
                self.rec_age[i] = ts
                self.rec_loc[i] = 0
            if self.rec_cnt[i] >= R:
                self.rec_loc[i], self.rec_row[i] = 1, len(self.mine_block)
                self.migrated.append(i)
                self.mine_block.append(block)
                self.mine_ts.append(self.rec_ts[i] + [0] * (S - R))
                self.mine_cnt.append(R)
        self.ts = ts + 1
        if self.full() and not self.defer_mining:
            self.mine()

    def full(self) -> bool:
        return len(self.mine_block) >= self.nm

    def mine(self) -> None:
        R, S, delta = self.R, self.S, self.delta
        valid = [R <= c <= S for c in self.mine_cnt]
        order = sorted(range(len(self.mine_cnt)), key=lambda r: self.mine_ts[r][0]
                       if valid[r] else INT32_MAX)
        pairs = []
        for a, i in enumerate(order):
            if not valid[i]:
                continue
            ti, ci = self.mine_ts[i], self.mine_cnt[i]
            first = True
            for j in order[a + 1: a + 1 + self.window]:
                if not valid[j] or self.mine_ts[j][0] - ti[0] > delta:
                    break
                if self.mine_cnt[j] != ci:
                    continue
                tj = self.mine_ts[j]
                d = [abs(tj[k] - ti[k]) for k in range(ci)]
                if max(d) > delta:
                    continue
                if first or 1 in d:
                    pairs.append((self.mine_block[i], self.mine_block[j]))
                    first = False
        for src, dst in pairs[: self.pairs_cap]:
            self.associate(src, dst)
        for i in self.migrated:
            if self.rec_loc[i] == 1:
                self.rec_key[i] = EMPTY
                self.rec_loc[i] = 0
        self.migrated = []
        self.mine_block, self.mine_ts, self.mine_cnt = [], [], []

    def associate(self, src: int, dst: int) -> None:
        base = bucket_of(src, self.pb) * self.pw
        i, found = self._locate(self.pf_key, self.pf_age, base, self.pw, src)
        if found:
            vals = self.pf_vals[i]
            if dst not in vals:
                vals[self.pf_cnt[i] % self.P] = dst
                self.pf_cnt[i] += 1
        else:
            self.pf_key[i] = src
            self.pf_vals[i] = [dst] + [EMPTY] * (self.P - 1)
            self.pf_cnt[i] = 1
        self.pf_age[i] = self.ts

    def lookup(self, block: int) -> List[int]:
        base = bucket_of(block, self.pb) * self.pw
        row = self.pf_key[base: base + self.pw]
        if block in row:
            return list(self.pf_vals[base + row.index(block)])
        return []


class _Amp:
    def __init__(self, c: dict):
        ns = self.n_streams = c["n_streams"]
        self.init_degree, self.max_degree = c["init_degree"], c["max_degree"]
        self.min_run = c["min_run"]
        self.last = [EMPTY] * ns
        self.seqlen = [0] * ns
        self.frontier = [EMPTY] * ns
        self.deg = [self.init_degree] * ns
        self.age = [0] * ns
        self.clock = 0

    def _owner(self, block: int) -> int:
        for s in range(self.n_streams):
            lo = self.frontier[s] - 2 * max(self.deg[s], 1)
            if lo <= block <= self.frontier[s] and self.last[s] != EMPTY:
                return s
        return -1

    def used(self, block: int) -> None:
        s = self._owner(block)
        if s >= 0:
            self.deg[s] = min(self.deg[s] + 1, self.max_degree)

    def evicted_unused(self, block: int) -> None:
        s = self._owner(block)
        if s >= 0:
            self.deg[s] = max(self.deg[s] - 1, 1)

    def access(self, block: int) -> List[int]:
        """Advance on a demand access; returns the blocks to prefetch.

        A stream continues when its last block is ``block - 1``; an unused
        slot holds EMPTY (-1), so block 0 continues an unused slot, as in
        the program's model.
        """
        self.clock += 1
        if block - 1 in self.last:
            s = self.last.index(block - 1)
            run, deg, front = self.seqlen[s] + 1, self.deg[s], \
                self.frontier[s]
            want = (run >= self.min_run
                    and block + max(deg // 2, 1) >= front)
            out = []
            if want:
                start, end = max(front, block) + 1, block + deg
                out = [start + k for k in range(self.max_degree)
                       if start + k <= end]
                self.frontier[s] = max(front, end)
            self.seqlen[s] = run
        else:
            s = _argmin(self.age)
            out = []
            self.seqlen[s], self.frontier[s] = 1, block
            self.deg[s] = self.init_degree
        self.last[s] = block
        self.age[s] = self.clock
        return out


CONTROLS = {
    "": "the reference",
    # the shortcut a faster cache probe tempts: 16-way sets under the
    # hashed index in place of the stated (fully) associative cache
    "sixteen_way_sets": "the cache as 16-way sets, not as stated",
    # the shortcut the batch-wide mining barrier tempts: mine a full
    # table only at the end of each slab, letting it fill past its size
    "mining_per_slab": "mining only at slab ends; the table overfills",
}


def check_supported(sim: dict) -> None:
    """Raise on a configuration whose semantics this reference lacks."""
    if sim["policy"] != "lru" or sim["use_pg"] or sim["use_learned"]:
        raise ValueError("the reference models LRU with MITHRIL and AMP "
                         f"only: {sim}")
    if sim["use_mithril"] and (sim["record_on"] != "miss"
                               or sim["symmetric"]):
        raise ValueError("the reference records on miss, asymmetric only")


def simulate(sim: dict, blocks, control: str = "", slab: int = 256
             ) -> Counts:
    """Counts of one volume under the configuration ``sim`` (a
    configuration file's keys).

    ``control`` breaks one stated guarantee on purpose, for the
    benchmark's controls (``CONTROLS``); empty is the reference itself.
    """
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; "
                         f"expected one of {sorted(CONTROLS)}")
    check_supported(sim)
    cache = _Cache(sim["capacity"],
                   16 if control == "sixteen_way_sets" else sim["ways"])
    defer = control == "mining_per_slab"
    mith = _Mithril(sim, defer_mining=defer) if sim["use_mithril"] else None
    amp = _Amp(sim) if sim["use_amp"] else None
    hits = 0
    issued, used, unused = [0] * N_SRC, [0] * N_SRC, [0] * N_SRC

    def prefetch(cands, src):
        evs = []
        for c in cands:
            if c == EMPTY or cache.contains(c):
                continue
            ev = cache.insert(c, 1, src)
            issued[src] += 1
            unused[ev[2]] += ev[1]
            evs.append(ev)
        return evs

    for n, block in enumerate(map(int, blocks), 1):
        cache.clock += 1
        b = cache.bucket(block)
        keys = cache.key[b]
        used_src, demand_ev = NONE, (EMPTY, False, NONE)
        if block in keys:
            hits += 1
            way = keys.index(block)
            if cache.flag[b][way] == 1 and cache.src[b][way] != NONE:
                used_src = cache.src[b][way]
                used[used_src] += 1
            cache.stamp[b][way] = cache.clock
            cache.flag[b][way], cache.src[b][way] = 0, NONE
        else:
            demand_ev = cache.insert(block, 0, NONE)
            unused[demand_ev[2]] += demand_ev[1]
            if mith is not None:
                mith.record(block)
        if mith is not None:
            prefetch(mith.lookup(block), MITHRIL)
        if amp is not None:
            if used_src == AMP:
                amp.used(block)
            for ev_block, ev_unused, ev_src in prefetch(amp.access(block),
                                                        AMP):
                if ev_unused and ev_src == AMP:
                    amp.evicted_unused(ev_block)
            if demand_ev[1] and demand_ev[2] == AMP:
                amp.evicted_unused(demand_ev[0])
        if defer and n % slab == 0 and mith.full():
            mith.mine()
    return Counts(len(blocks), hits, tuple(issued), tuple(used),
                  tuple(unused))




def simulate_flat(sim: dict, blocks, control: str = "", slab: int = 256
                  ) -> List[int]:
    """:func:`simulate`'s counts as one flat list (a picklable task)."""
    return simulate(sim, blocks, control, slab).flat()
