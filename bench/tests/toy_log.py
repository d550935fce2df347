"""A toy driver for the tests: a block log with reads and writes of
several sizes, a deployment of other semantics than the block sweep.

Its traffic gives ``write_share``, the request ``sizes`` in blocks and
the ``length_fracs`` of the cell's ``length`` that a job's slots take in
turn. A volume carries an op and a size column beside its block ids.
The program call counts, per volume, requests, reads, writes and the
bytes written; the plain reference counts the same in a Python loop.
"""

from __future__ import annotations

import numpy as np

from bench.lib import generate

CONTROLS = {
    "": "the reference",
    "blocks_not_bytes": "writes counted in blocks, not in bytes",
}


def check(config: dict) -> None:
    if int(config.get("block_bytes", 0)) <= 0:
        raise ValueError(f"block_bytes must be positive: {config}")


def program(config: dict):
    import jax
    import jax.numpy as jnp

    block_bytes = int(config["block_bytes"])

    @jax.jit
    def count(op, size, valid):
        w = valid & (op == 1)
        r = valid & (op == 0)
        return jnp.stack([valid.sum(1), r.sum(1), w.sum(1),
                          jnp.where(w, size, 0).sum(1) * block_bytes], 1)
    return count


def make_pool(cell, seed: int):
    t, shape = cell.traffic, cell.shape
    pool = []
    for job in range(shape["pool_jobs"]):
        vols = []
        for slot in range(shape["volumes_per_job"]):
            rng = np.random.default_rng(generate.volume_seed(job, slot))
            frac = t["length_fracs"][slot % len(t["length_fracs"])]
            n = max(1, int(shape["length"] * frac))
            blocks = (rng.integers(0, 1 << 20, n)
                      + generate.volume_offset(seed, job, slot)
                      ) % generate.ADDRESS_SPACE
            vols.append({
                "blocks": blocks.astype(np.int32),
                "op": (rng.random(n) < t["write_share"]).astype(np.int32),
                "size": rng.choice(t["sizes"], n).astype(np.int32)})
        pool.append(vols)
    return pool


def _padded(volumes, length: int):
    op = np.zeros((len(volumes), length), np.int32)
    size = np.zeros((len(volumes), length), np.int32)
    valid = np.zeros((len(volumes), length), bool)
    for i, v in enumerate(volumes):
        n = len(v["op"])
        op[i, :n], size[i, :n], valid[i, :n] = v["op"], v["size"], True
    return op, size, valid


def warm_up(count, shape: dict) -> None:
    one = {"op": np.zeros(1, np.int32), "size": np.ones(1, np.int32)}
    np.asarray(count(*_padded([one] * shape["volumes_per_job"],
                              shape["length"])))


def run_job(count, volumes, shape: dict) -> dict:
    before = count._cache_size()
    counts = np.asarray(count(*_padded(volumes, shape["length"])), np.int64)
    requests = int(counts[:, 0].sum())
    return {"counts": counts, "requests": requests, "n_slabs": 1,
            "lane_steps": len(volumes) * shape["length"],
            "ideal_lane_steps": requests, "stats": {},
            "compiles": count._cache_size() - before}


def reference(config: dict, shape: dict, volume, control: str = ""):
    unit = 1 if control == "blocks_not_bytes" else config["block_bytes"]
    reads = writes = written = 0
    for op, size in zip(volume["op"].tolist(), volume["size"].tolist()):
        if op == 1:
            writes += 1
            written += size * unit
        else:
            reads += 1
    return [reads + writes, reads, writes, written]
