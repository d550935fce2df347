"""Pallas TPU kernel: MITHRIL pairwise association check over a lanes axis.

Batched sibling of ``mithril_mine`` for the sweep engine's mining barrier
(DESIGN.md §7): when the batch-level trigger fires, EVERY lane flagged for
mining runs its (rows x window x S) timestamp comparison in one kernel
launch instead of a ``fori_loop``-of-``lax.cond`` over lanes.

Grid layout: ``(lanes, n_row_blocks)``. Each program holds ONE lane's
whole (padded) timestamp matrix in VMEM — mining tables are small by
construction (paper: 1250 rows x S=8 -> ~40KB at int32), so even dozens
of lanes stream comfortably under the ~16MB VMEM budget — and compares
its (BLK, S) row tile against ``window`` statically-shifted row slabs,
exactly like the serial kernel (same ``_codes_tile`` math, DESIGN.md §2).

Input rows must be pre-padded per lane with ``window`` trailing invalid
rows and to a BLK multiple (``ops.mithril_pairwise_batched`` does this).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .backend import default_interpret
from .mithril_mine import _codes_tile


def _mine_kernel_batched(ts_ref, cnt_ref, valid_ref, out_ref, *, delta: int,
                         window: int, blk: int):
    """Grid: (lanes, n_row_blocks). Refs carry a leading lane dim of 1."""
    r0 = pl.multiple_of(pl.program_id(1) * blk, blk)
    out_ref[0] = _codes_tile(ts_ref, cnt_ref, valid_ref, r0, blk=blk,
                             window=window, delta=delta, lead=(0,))


def pairwise_codes_batched_kernel(ts: jax.Array, cnt: jax.Array,
                                  valid: jax.Array, delta: int, window: int,
                                  *, blk: int = 128,
                                  interpret: Optional[bool] = None
                                  ) -> jax.Array:
    """ts: (L, N_pad, S) int32, each lane sorted by ts[l,:,0] and padded
    with >= window invalid rows; cnt/valid: (L, N_pad, 1) int32. Returns
    (L, N, W) codes where N = N_pad - window - 1 ... callers slice. See
    ``ops.mithril_pairwise_batched``.

    ``interpret=None`` resolves from the backend: compiled on TPU,
    interpreted elsewhere (never silently interpreted on real hardware).
    """
    interpret = default_interpret(interpret)
    lanes, n_pad, s = ts.shape
    n_rows = n_pad - window - 1
    assert n_rows % blk == 0, (n_rows, blk)
    grid = (lanes, n_rows // blk)
    kernel = functools.partial(_mine_kernel_batched, delta=delta,
                               window=window, blk=blk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, n_pad, s), lambda l, i: (l, 0, 0)),   # lane VMEM
            pl.BlockSpec((1, n_pad, 1), lambda l, i: (l, 0, 0)),
            pl.BlockSpec((1, n_pad, 1), lambda l, i: (l, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk, window), lambda l, i: (l, i, 0)),
        out_shape=jax.ShapeDtypeStruct((lanes, n_rows, window), jnp.int32),
        interpret=interpret,
        name="mithril_mine_batched",
    )(ts, cnt, valid)
