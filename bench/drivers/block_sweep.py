"""The default driver: a batched trace sweep of a block cache.

The configuration's keys are the fields of the program's ``SimConfig``,
``MithrilConfig`` and ``AmpConfig``; the traffic file is
``bench/lib/generate.py``'s; the cell's shape gives ``lanes``,
``volumes_per_job``, ``nominal_length``, ``chunk`` and ``pool_jobs``. A
job is one ``sweep_streaming`` call over the job's volumes through the
cell's recycled lanes, every request arrived at step 0. Its counts are
14 a volume (:func:`counts_of`), compared with
``bench/lib/reference.py``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench.lib import generate
from bench.lib import reference as plain

# Slabs the streaming engine stages and dispatches ahead of the device:
# at ~0.32 s of device work a slab in both cells, some 8 s, so that a
# host that stands still for a few seconds leaves the chip fed. Each
# slab in flight holds its own output carry on the device (~97 MB).
RING_DEPTH = 24

CONTROLS = plain.CONTROLS


def check(config: dict) -> None:
    plain.check_supported(config)


def sim_config(config: dict):
    """The program's ``SimConfig`` for a configuration file, whose keys
    are the fields of ``SimConfig``, ``MithrilConfig`` and ``AmpConfig``."""
    from repro.cache import SimConfig
    from repro.cache.amp import AmpConfig
    from repro.core import MithrilConfig

    def pick(cls):
        return {f.name: config[f.name] for f in dataclasses.fields(cls)
                if f.name in config}

    return SimConfig(**{k: v for k, v in pick(SimConfig).items()
                        if k not in ("mithril", "amp", "pg", "learned")},
                     mithril=MithrilConfig(**pick(MithrilConfig)),
                     amp=AmpConfig(**pick(AmpConfig)))


program = sim_config


def make_pool(cell, seed: int):
    shape = cell.shape
    return [generate.make_job(cell.traffic, seed, j,
                              shape["volumes_per_job"],
                              shape["nominal_length"])
            for j in range(shape["pool_jobs"])]


def counts_of(stats) -> np.ndarray:
    """(volumes, 14): requests, hits, then issued, used and evicted
    unused per prefetch source."""
    return np.concatenate(
        [np.asarray(stats.requests)[:, None], np.asarray(stats.hits)[:, None],
         np.asarray(stats.pf_issued), np.asarray(stats.pf_used),
         np.asarray(stats.pf_evicted_unused)], axis=1).astype(np.int64)


def _sweep(cfg, volumes, shape: dict):
    from repro.cache.sweep import sweep_streaming

    return sweep_streaming(cfg, [v.blocks for v in volumes],
                           lane_width=shape["lanes"], chunk=shape["chunk"],
                           ring_depth=RING_DEPTH)


def warm_up(cfg, shape: dict) -> None:
    """Run the cell's ``(chunk, lanes)`` runner and the lane reset once:
    ``lanes + 1`` one-slab volumes, so one lane is recycled."""
    chunk, lanes = shape["chunk"], shape["lanes"]
    blocks = np.arange(chunk, dtype=np.int32)
    out = _sweep(cfg, [generate.Volume("warm", {}, blocks + i * chunk)
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


def reference(config: dict, shape: dict, volume, control: str = ""):
    """The plain reference's 14 counts of one volume; a control that
    mines at slab ends takes the cell's chunk as the slab."""
    return plain.simulate_flat(config, volume.blocks, control,
                               shape["chunk"])
