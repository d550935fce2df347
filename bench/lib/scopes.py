"""The chunk runner's parts by the named scopes the program gives them.

The sweep engine runs each part of a scan step under a
``jax.named_scope`` (``repro.cache.sweep.build_batched_step``), and XLA
writes the name stack into the op_name of every operation a part lowers
to, layout copies included: a copy XLA inserts to hand a part's output
to another takes the producing part's op_name. An operation belongs to
the innermost of ``SCOPES`` in its op_name; one with none of them is
unscoped: the loop's own operations, operations XLA gives no op_name
(the asynchronous ``copy-start``/``copy-done`` pairs) and operations
outside the runner.

The device trace keeps each operation's op_name in a stat of the event's
metadata, which ``ProfileData`` does not expose, so the op_names come
from the runner's compiled HLO text instead: the cell's runner is
compiled again in the run's own process (the persistent compilation
cache holds it since the warm-up) and joined to the trace by each
operation's name, opcode and result shape (:func:`trace.describe`).
"""

from __future__ import annotations

import re
from typing import Dict, Optional

from bench.lib import trace
from bench.lib.kernels import kernel_launches

SCOPES = ("access", "record", "barrier", "prefetch", "amp")
UNSCOPED = "unscoped"

INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?(%[\w.\-]+ = .*)$")
OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


def op_names(hlo_text: str) -> Dict[str, str]:
    """Each instruction's short name (``trace.describe``) -> its
    op_name, for the instructions of compiled HLO text that have one."""
    out = {}
    for line in hlo_text.splitlines():
        m = INSTRUCTION.match(line)
        name = m and OP_NAME.search(line)
        if name:
            out[trace.describe(m[1])] = name[1]
    return out


def scope_of(op_name: str) -> Optional[str]:
    """The innermost of ``SCOPES`` among the op_name's path components
    (a component may wrap the scope in a transform: ``vmap(amp)``)."""
    for part in reversed(op_name.split("/")):
        word = part.rstrip(")").rsplit("(", 1)[-1]
        if word in SCOPES:
            return word
    return None


def scope_seconds(summary, names: Dict[str, str]
                  ) -> Optional[Dict[str, float]]:
    """Own device seconds of the traced span per scope, and under
    ``UNSCOPED`` the rest, so that the values add up to the busy time.
    ``None`` where no operation carries a scope: a program that names
    none."""
    out = dict.fromkeys(SCOPES + (UNSCOPED,), 0.0)
    for op, seconds in summary.op_seconds().items():
        scope = scope_of(names.get(trace.describe(op), ""))
        out[scope or UNSCOPED] += seconds
    if not any(out[s] for s in SCOPES):
        return None
    return out


_HLO: Dict[str, str] = {}


def runner_hlo(cell) -> str:
    """The compiled HLO text of the cell's chunk runner, built as the
    window's jobs build it: the cell's configuration, lanes and chunk,
    on one device."""
    if cell.name not in _HLO:
        import importlib

        import jax
        import numpy as np

        from bench.drivers.block_sweep import sim_config

        sweep = importlib.import_module("repro.cache.sweep")
        lanes, chunk = cell.shape["lanes"], cell.shape["chunk"]
        if sweep._lane_shards(lanes, None) != 1:
            raise NotImplementedError("scope metrics read one-chip cells")
        init_batched, run_chunk, place = sweep._runner(
            sim_config(cell.config), 1, 1)
        slab = jax.device_put((np.zeros((chunk, lanes), np.int32),
                               np.zeros((chunk, lanes), bool)))
        _HLO[cell.name] = run_chunk.lower(
            place(init_batched(lanes)), *slab).compile().as_text()
    return _HLO[cell.name]


def ms_per_step(run, scope: str) -> Optional[float]:
    """Device milliseconds per scan step of ``scope`` (or ``UNSCOPED``)
    in the traced span; every scan step launches the record kernel once,
    so its launches count the steps."""
    t = run.trace
    if t is None:
        return None
    split = scope_seconds(t, op_names(runner_hlo(run.cell)))
    if split is None:
        return None
    return 1e3 * split[scope] / kernel_launches(t, "record")
