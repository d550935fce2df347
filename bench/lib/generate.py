"""Seeded block-trace volumes for the benchmark, built from a traffic file.

The family generators are copies of the repository's synthetic trace
generators (``traces/synthetic.py``), kept here so that later changes to
the program's trace code do not move the yardstick.

A traffic file (``bench/traffic/<name>.json``) lists ``volumes``: each
entry names a ``family`` (a key of :data:`BUILDERS`), the generator's
``params`` and a ``length_frac`` of the cell's nominal volume length. A
job's slots take the entries in order, cycling. Every request of a job
has arrived at step 0 (a closed loop of whole jobs).

What the run's ``--seed`` changes: where each volume lies in the block
space. A volume's access pattern is generated from its job and slot
alone, and the seed shifts the whole volume by an offset of its own
(modulo the 2**30-block space). So every seed gives the same work: the
same lengths, reuse distances, sequential runs, the same points at
which MITHRIL's mining tables fill, and so the same mining passes, and
the same lanes filling on the same step across the batch, where the
engine's one-lane and all-lane mining paths differ in cost by two
orders. What differs from seed to seed is every address, and with it
every hash bucket of the recording and prefetching tables.
"""

from __future__ import annotations

from typing import List, NamedTuple

import numpy as np


# --- family generators (copied from traces/synthetic.py) -----------------

def interleaved_sequential(n_requests: int, n_streams: int = 8,
                           run_len: int = 24, lba_space: int = 1 << 22,
                           skip_prob: float = 0.12,
                           seed: int = 0) -> np.ndarray:
    """Concurrent sequential streams, round-robin with random stalls."""
    rng = np.random.default_rng(seed)
    pos = rng.integers(0, lba_space, size=n_streams)
    left = rng.integers(1, run_len, size=n_streams)
    out = np.empty(n_requests, np.int64)
    for i in range(n_requests):
        s = rng.integers(n_streams)
        if left[s] == 0:  # stream jumps to a new extent
            pos[s] = rng.integers(0, lba_space)
            left[s] = rng.integers(run_len // 2, run_len)
        out[i] = pos[s]
        step = 1 if rng.random() >= skip_prob else rng.integers(2, 5)
        pos[s] += step
        left[s] -= 1
    return (out % (1 << 30)).astype(np.int32)


def association_groups(n_requests: int, n_groups: int = 200,
                       group_size: int = 4, reuse: int = 8,
                       spread: int = 3, lba_space: int = 1 << 22,
                       seed: int = 0) -> np.ndarray:
    """Scattered block groups re-accessed together ``reuse`` times."""
    rng = np.random.default_rng(seed)
    groups = [np.sort(rng.choice(lba_space, size=group_size, replace=False))
              for _ in range(n_groups)]
    events: List[np.ndarray] = []
    for g in groups:
        for _ in range(reuse):
            order = rng.permutation(group_size)
            events.append(g[order])
    rng.shuffle(events)
    out: List[int] = []
    queue: List[int] = []
    for ev in events:
        queue.extend(ev.tolist())
        while len(queue) > spread:
            out.append(queue.pop(0))
    out.extend(queue)
    arr = np.asarray(out[:n_requests], np.int64)
    if len(arr) < n_requests:  # pad by tiling
        arr = np.resize(arr, n_requests)
    return (arr % (1 << 30)).astype(np.int32)


def looping(n_requests: int, loop_len: int = 800, n_loops: int = 4,
            jitter: float = 0.02, lba_space: int = 1 << 22,
            seed: int = 0) -> np.ndarray:
    """Cyclic scans: repeated sequential passes over fixed regions."""
    rng = np.random.default_rng(seed)
    base = rng.integers(0, lba_space, size=n_loops)
    which = rng.integers(0, n_loops, size=n_requests)
    counts = np.bincount(which, minlength=n_loops)
    order = np.argsort(which, kind="stable")
    starts = np.cumsum(counts) - counts
    ranks = np.empty(n_requests, np.int64)
    ranks[order] = np.arange(n_requests) - np.repeat(starts, counts)
    pos = ranks % max(1, loop_len)
    skip = np.where(rng.random(n_requests) < jitter,
                    rng.integers(1, 4, size=n_requests), 0)
    out = base[which].astype(np.int64) + pos + skip
    return (out % (1 << 30)).astype(np.int32)


def zipf(n_requests: int, catalog: int = 1 << 16, alpha: float = 1.1,
         seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    ranks = rng.zipf(alpha, size=n_requests)
    return (np.minimum(ranks, catalog) - 1).astype(np.int32)


def mixed(n_requests: int, w_seq: float = 0.3, w_assoc: float = 0.4,
          w_zipf: float = 0.3, seed: int = 0) -> np.ndarray:
    """Weighted interleave; address spaces offset so parts don't alias."""
    rng = np.random.default_rng(seed)
    n_s = int(n_requests * w_seq)
    n_a = int(n_requests * w_assoc)
    n_z = n_requests - n_s - n_a
    parts = []
    if n_s:
        parts.append(interleaved_sequential(n_s, seed=seed + 1))
    if n_a:
        parts.append(association_groups(n_a, seed=seed + 2) + (1 << 26))
    if n_z:
        parts.append(zipf(n_z, seed=seed + 3) + (1 << 28))
    idx = np.concatenate([np.full(len(p), i) for i, p in enumerate(parts)])
    rng.shuffle(idx)
    cursors = [0] * len(parts)
    out = np.empty(n_requests, np.int32)
    for i, which in enumerate(idx):
        out[i] = parts[which][cursors[which]]
        cursors[which] += 1
    return out


BUILDERS = {"seq": interleaved_sequential, "loop": looping, "zipf": zipf,
            "midfreq": association_groups, "mixed": mixed}


# --- jobs -----------------------------------------------------------------

class Volume(NamedTuple):
    family: str
    params: dict
    blocks: np.ndarray                  # (length,) int32


ADDRESS_SPACE = 1 << 30


def _draw(*key: int) -> int:
    ss = np.random.SeedSequence([int(k) % (1 << 64) for k in key])
    return int(ss.generate_state(1)[0])


def volume_seed(job: int, slot: int) -> int:
    """The 31-bit generator seed of one slot of one job: the same in
    every run."""
    return _draw(job, slot) & 0x7FFFFFFF


def volume_offset(seed: int, job: int, slot: int) -> int:
    """Where the run ``seed`` places one slot of one job in the block
    space."""
    return _draw(seed, job, slot) % ADDRESS_SPACE


def make_job(traffic: dict, seed: int, job: int, n_volumes: int,
             nominal_length: int) -> List[Volume]:
    """The ``n_volumes`` volumes of one job, in submission order."""
    entries = traffic["volumes"]
    for e in entries:
        if e["family"] not in BUILDERS:
            raise ValueError(f"unknown family {e['family']!r}; "
                             f"expected one of {sorted(BUILDERS)}")
    out = []
    for slot in range(n_volumes):
        e = entries[slot % len(entries)]
        length = max(1, int(nominal_length * e["length_frac"]))
        blocks = BUILDERS[e["family"]](
            length, seed=volume_seed(job, slot), **e["params"])
        blocks = (blocks.astype(np.int64) + volume_offset(seed, job, slot)
                  ) % ADDRESS_SPACE
        out.append(Volume(e["family"], dict(e["params"]),
                          blocks.astype(np.int32)))
    return out
