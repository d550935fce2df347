"""Pallas kernel: batched set-associative prefetch-table probe.

Per-request MITHRIL work is one hash probe (Alg. 3 pFlag path). When the
serving layer batches requests (pages/experts for a whole decode step),
the probes batch too: grid ``(queries,)``, one probe per program. The
wrapper computes each query's bucket ``mix32(q) & (NB-1)`` and
scalar-prefetches it, so each program's BlockSpec DMAs only the
128-wide bucket window of the lane-dense tables (``keys`` as ``(W, NB)``,
``vals`` as ``(W, P, NB)``: buckets on the lane axis). Inside, the
bucket is a one-hot lane mask and the hit way a masked min over the
sublanes — compare-and-reduce, no vector gather.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import default_interpret
from .mithril_record import bucket_window


def _lookup_kernel(q_ref, bkt_ref, keys_ref, vals_ref, out_ref, *,
                   ways: int, plist: int, win: int):
    i32 = jnp.int32
    i = pl.program_id(0)
    q = jnp.full((1, 1), q_ref[i], i32)
    col = jnp.full((1, 1), bkt_ref[i] % win, i32)
    keys = keys_ref[...]                                 # (W, win)
    at_b = jax.lax.broadcasted_iota(i32, (ways, win), 1) == col
    kcol = jnp.sum(jnp.where(at_b, keys, 0), axis=1, keepdims=True)
    hit = kcol == q                                      # (W, 1)
    found = jnp.max(jnp.where(hit, 1, 0), axis=0, keepdims=True) > 0
    way = jnp.min(jnp.where(hit, jax.lax.broadcasted_iota(i32, (ways, 1), 0),
                            ways), axis=0, keepdims=True)
    shape = (ways, plist, win)
    at = (jax.lax.broadcasted_iota(i32, shape, 0) == way) \
        & (jax.lax.broadcasted_iota(i32, shape, 2) == col)
    picked = jnp.sum(jnp.where(at, vals_ref[...], 0), axis=(0, 2),
                     keepdims=True)                      # (1, P, 1)
    out_ref[...] = jnp.where(found, picked, -1)


def hash_lookup_kernel(queries: jax.Array, buckets: jax.Array,
                       keys: jax.Array, vals: jax.Array, *,
                       interpret: Optional[bool] = None) -> jax.Array:
    """queries/buckets: (Q,) int32; keys: (W, NB); vals: (W, P, NB).
    Returns (Q, P, 1) prefetch candidates (-1 = none).
    ``interpret=None``: compiled on TPU, interpreted elsewhere."""
    interpret = default_interpret(interpret)
    q = queries.shape[0]
    ways, nb = keys.shape
    plist = vals.shape[1]
    win = bucket_window(nb)
    kernel = functools.partial(_lookup_kernel, ways=ways, plist=plist,
                               win=win)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(q,),
            in_specs=[
                pl.BlockSpec((ways, win), lambda i, qs, bk: (0, bk[i] // win)),
                pl.BlockSpec((ways, plist, win),
                             lambda i, qs, bk: (0, 0, bk[i] // win)),
            ],
            out_specs=pl.BlockSpec((1, plist, 1),
                                   lambda i, qs, bk: (i, 0, 0))),
        out_shape=jax.ShapeDtypeStruct((q, plist, 1), jnp.int32),
        interpret=interpret,
        name="hash_lookup",
    )(queries, buckets, keys, vals)
