"""A write-back block cache under reads and writes of several blocks.

The configuration is ``block_sweep``'s (the fields of the program's
``SimConfig`` and ``MithrilConfig``) with ``"write_back": true``: a
write marks its block dirty, on a hit or as the fill of a miss
(write-allocate); a prefetched block enters clean; a dirty block is
written back once, when a demand or a prefetch insertion pushes it out,
or at the end of its volume as the volume's flush.

The traffic file gives the volume kinds a job's slots take in turn
(:func:`make_volume`): each names a family of :data:`FAMILIES` whose
draws are 64 KB extents, a write share and weights of request sizes in
blocks. :data:`FAMILIES` are ``bench/lib/generate.py``'s and
:func:`finite_zipf`, a Zipf law over a finite catalog, which takes any
exponent: ``generate.zipf`` draws NumPy's unbounded Zipf, defined only
above 1, and clips it to the catalog, piling the tail on its last rank. A request covers the first ``size`` blocks of its
extent and every block of a write is a write, as
``traces.io.ingest_msr_csv`` expands an MSR Cambridge record. A volume
is a dict of NumPy arrays, ``blocks`` (int32) and ``writes`` (bool).

A job is one ``sweep_streaming(..., writes=)`` call through the cell's
recycled lanes. Its counts are 18 a volume: ``block_sweep``'s 14, then
writes, write hits, write-backs and the dirty blocks flushed at the end,
compared with :func:`simulate`, a plain write-back LRU loop in this
file that imports nothing of the program.
"""

from __future__ import annotations

import numpy as np

from bench.drivers import block_sweep
from bench.drivers.block_sweep import RING_DEPTH, sim_config
from bench.lib import generate
from bench.lib import reference as plain

EXTENTS = 1 << 26           # 64 KB extents of 16 blocks in 2**30 blocks


def finite_zipf(n_requests: int, catalog: int = 1 << 16,
                alpha: float = 0.9, seed: int = 0) -> np.ndarray:
    """Ranks ``0 .. catalog - 1``, rank ``k - 1`` drawn with probability
    proportional to ``k ** -alpha``, by inverting the catalog's CDF."""
    rng = np.random.default_rng(seed)
    cdf = np.cumsum(np.arange(1, catalog + 1, dtype=np.float64) ** -alpha)
    ranks = np.searchsorted(cdf, rng.random(n_requests) * cdf[-1],
                            side="right")
    return np.minimum(ranks, catalog - 1).astype(np.int32)


FAMILIES = {**generate.BUILDERS, "finite_zipf": finite_zipf}

CONTROLS = {
    "": "the reference",
    # the cache written through: a write-back at every write, none owed
    "write_through": "a write-back at every write, not once per dirtying",
    # the shortcut the prefetch path tempts: its victim taken as clean
    "prefetch_evicts_clean":
        "a dirty block a prefetch insert pushes out is dropped unwritten",
    # dirty blocks left at the end of a volume never written back
    "no_end_flush": "dirty blocks resident at a volume's end not counted",
}


def make_volume(traffic: dict, entry: dict, seed: int, job: int,
                slot: int, nominal_length: int) -> dict:
    """One volume: ``length_frac`` x ``nominal_length`` block accesses.

    Sizes and write flags come from the job and slot alone, the family's
    draws too; the run's ``seed`` shifts the volume's extents whole, as
    ``generate.make_job`` shifts its blocks (so every seed gives the same
    work at other addresses)."""
    length = max(1, int(nominal_length * entry["length_frac"]))
    vseed = generate.volume_seed(job, slot)
    rng = np.random.default_rng([vseed, 1])
    sizes, weights = np.asarray(entry["sizes"], np.float64).T
    sizes = rng.choice(sizes.astype(np.int64), size=length,
                       p=weights / weights.sum())
    n_req = int(np.searchsorted(np.cumsum(sizes), length)) + 1
    sizes = sizes[:n_req]
    is_write = rng.random(n_req) < entry["write_share"]
    draws = FAMILIES[entry["family"]](n_req, seed=vseed, **entry["params"])
    extents = (draws.astype(np.int64)
               + generate.volume_offset(seed, job, slot)) % EXTENTS
    starts = np.cumsum(sizes) - sizes
    within = np.arange(int(sizes.sum())) - np.repeat(starts, sizes)
    blocks = np.repeat(extents * traffic["extent_blocks"], sizes) + within
    return {"kind": entry["kind"], "blocks": blocks[:length].astype(np.int32),
            "writes": np.repeat(is_write, sizes)[:length]}


def check_supported(config: dict) -> None:
    """Raise on a configuration whose semantics :func:`simulate` lacks."""
    plain.check_supported(config)
    if not config.get("write_back"):
        raise ValueError("rw_sweep runs write-back configurations "
                         "(\"write_back\": true)")
    if config["use_amp"]:
        raise ValueError("the write-back reference models MITHRIL "
                         "without AMP")


def check(config: dict) -> None:
    import dataclasses

    from repro.cache import SimConfig

    if "write_back" not in {f.name for f in dataclasses.fields(SimConfig)}:
        raise ValueError("the program's SimConfig has no write_back: it "
                         "cannot run a write-back cache")
    check_supported(config)


program = sim_config


def make_pool(cell, seed: int):
    shape, entries = cell.shape, cell.traffic["volumes"]
    return [[make_volume(cell.traffic, entries[slot % len(entries)], seed,
                         j, slot, shape["nominal_length"])
             for slot in range(shape["volumes_per_job"])]
            for j in range(shape["pool_jobs"])]


def counts_of(stats) -> np.ndarray:
    """(volumes, 18): ``block_sweep``'s 14, then writes, write hits,
    write-backs and the dirty blocks flushed at the end."""
    wb = [stats.writes, stats.write_hits, stats.writebacks,
          stats.dirty_resident]
    return np.concatenate(
        [block_sweep.counts_of(stats),
         np.stack([np.asarray(c) for c in wb], axis=1)],
        axis=1).astype(np.int64)


def _sweep(cfg, volumes, shape: dict):
    from repro.cache.sweep import sweep_streaming

    return sweep_streaming(cfg, [v["blocks"] for v in volumes],
                           writes=[v["writes"] for v in volumes],
                           lane_width=shape["lanes"], chunk=shape["chunk"],
                           ring_depth=RING_DEPTH)


def warm_up(cfg, shape: dict) -> None:
    """Run the cell's ``(chunk, lanes)`` runner and the lane reset once:
    ``lanes + 1`` one-slab volumes of reads and writes, so one lane is
    recycled."""
    chunk, lanes = shape["chunk"], shape["lanes"]
    blocks = np.arange(chunk, dtype=np.int32)
    writes = blocks % 2 == 1
    out = _sweep(cfg, [{"blocks": blocks + i * chunk, "writes": writes}
                       for i in range(lanes + 1)], shape)
    counts_of(out.result.stats)


def run_job(cfg, volumes, shape: dict) -> dict:
    from jax.profiler import TraceAnnotation

    out = _sweep(cfg, volumes, shape)
    with TraceAnnotation("readback"):
        counts = counts_of(out.result.stats)
    st = out.streaming_stats()
    return {"counts": counts, "requests": int(counts[:, 0].sum()),
            "n_slabs": out.n_slabs, "lane_steps": st["lane_steps"],
            "ideal_lane_steps": st["ideal_lane_steps"], "stats": st,
            "compiles": out.result.compiles}


def simulate(sim: dict, blocks, writes, control: str = "") -> list:
    """The 18 counts of one volume under the write-back configuration
    ``sim``: ``reference.simulate``'s LRU and MITHRIL, request by request,
    with the set of dirty resident blocks beside the cache. ``control``
    breaks one stated guarantee (``CONTROLS``)."""
    if control not in CONTROLS:
        raise ValueError(f"unknown control {control!r}; "
                         f"expected one of {sorted(CONTROLS)}")
    check_supported(sim)
    cache = plain._Cache(sim["capacity"], sim["ways"])
    mith = plain._Mithril(sim) if sim["use_mithril"] else None
    n_src = plain.N_SRC
    issued, used, unused = [0] * n_src, [0] * n_src, [0] * n_src
    dirty = set()
    hits = n_writes = write_hits = writebacks = 0

    def leave(ev, by_prefetch: bool) -> None:
        """Account the block an insertion pushed out."""
        nonlocal writebacks
        block, was_unused, src = ev
        unused[src] += was_unused
        if block in dirty:
            dirty.discard(block)
            if not (by_prefetch and control == "prefetch_evicts_clean"):
                writebacks += 1

    for block, write in zip(map(int, blocks), map(bool, writes)):
        cache.clock += 1
        b = cache.bucket(block)
        keys = cache.key[b]
        if block in keys:
            hits += 1
            write_hits += write
            way = keys.index(block)
            if cache.flag[b][way] == 1 and cache.src[b][way] != plain.NONE:
                used[cache.src[b][way]] += 1
            cache.stamp[b][way] = cache.clock
            cache.flag[b][way], cache.src[b][way] = 0, plain.NONE
        else:
            leave(cache.insert(block, 0, plain.NONE), False)
            if mith is not None:
                mith.record(block)
        if write:
            n_writes += 1
            if control == "write_through":
                writebacks += 1
            else:
                dirty.add(block)
        if mith is not None:
            for c in mith.lookup(block):
                if c == plain.EMPTY or cache.contains(c):
                    continue
                issued[plain.MITHRIL] += 1
                leave(cache.insert(c, 1, plain.MITHRIL), True)
    flushed = 0 if control == "no_end_flush" else len(dirty)
    return [len(blocks), hits, *issued, *used, *unused, n_writes,
            write_hits, writebacks, flushed]


def reference(config: dict, shape: dict, volume, control: str = ""):
    """The plain reference's 18 counts of one volume."""
    return simulate(config, volume["blocks"], volume["writes"], control)
