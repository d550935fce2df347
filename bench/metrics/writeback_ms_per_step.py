"""Device milliseconds per scan step of a write-back cache's bookkeeping:
the own time in the traced span of the operations whose op_name has a
``writeback`` component (the victim's dirty read, the dirty-row updates
and the write-back counters, in the demand access and in the prefetch
inserts), over the record kernel's launches, one a step.

The op_names come from the cell's runner compiled in the run's own
process, as ``bench/lib/scopes.py`` joins them, but lowered here with the
write column as its third slab (``scopes.runner_hlo`` lowers the
two-slab runner of a read-only cache). A configuration or a program
without a write-back cache, or a runner with no operation under the
scope, reads nothing."""

from typing import Dict, Optional

from bench.lib import scopes, trace
from bench.lib.kernels import kernel_launches

SCOPE = "writeback"
_HLO: Dict[str, Optional[str]] = {}


def in_scope(op_name: str) -> bool:
    """Whether a path component of ``op_name`` is the scope, bare or
    wrapped in a transform (``vmap(writeback)``)."""
    return any(part.rstrip(")").rsplit("(", 1)[-1] == SCOPE
               for part in op_name.split("/"))


def runner_hlo(cell) -> Optional[str]:
    """Compiled HLO text of the cell's write-back runner on one device;
    ``None`` where the program builds no write-back cache for it."""
    if cell.name not in _HLO:
        import importlib

        import jax
        import numpy as np

        from bench.drivers.block_sweep import sim_config

        cfg = sim_config(cell.config)
        if not getattr(cfg, "write_back", False):
            _HLO[cell.name] = None
            return None
        sweep = importlib.import_module("repro.cache.sweep")
        lanes, chunk = cell.shape["lanes"], cell.shape["chunk"]
        if sweep._lane_shards(lanes, None) != 1:
            raise NotImplementedError("scope metrics read one-chip cells")
        init_batched, run_chunk, place = sweep._runner(cfg, 1, 1)
        slab = jax.device_put((np.zeros((chunk, lanes), np.int32),
                               np.zeros((chunk, lanes), bool),
                               np.zeros((chunk, lanes), bool)))
        _HLO[cell.name] = run_chunk.lower(
            place(init_batched(lanes)), *slab).compile().as_text()
    return _HLO[cell.name]


def ms_per_step(summary, hlo_text: str) -> Optional[float]:
    names = scopes.op_names(hlo_text)
    seconds = sum(s for op, s in summary.op_seconds().items()
                  if in_scope(names.get(trace.describe(op), "")))
    if not seconds:
        return None
    return 1e3 * seconds / kernel_launches(summary, "record")


def read(run):
    if run.trace is None:
        return None
    hlo = runner_hlo(run.cell)
    return None if hlo is None else ms_per_step(run.trace, hlo)
