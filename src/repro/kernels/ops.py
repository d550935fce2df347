"""Jit'd public wrappers around the Pallas kernels.

The interpret flag threads from backend detection (kernels/backend.py):
compiled on TPU, interpreted elsewhere (this container is CPU-only; the
kernels target TPU — DESIGN.md §2). The wrappers adapt the core data
layouts (padding, 2-D scalar arrays) to the kernel contracts.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.hashindex import bucket_of

from .backend import default_interpret
from .hash_lookup import hash_lookup_kernel
from .mithril_mine import pairwise_codes_kernel
from .mithril_record import record_step_kernel
from .paged_decode import paged_decode_kernel


def _mine_padding(n: int, window: int, blk: int):
    """Row padding so shifted slices stay in range and rows tile by blk."""
    blk = min(blk, max(8, 1 << (n - 1).bit_length()))
    n_rows = ((n + blk - 1) // blk) * blk
    return blk, n_rows, n_rows + window + 1


@functools.partial(jax.jit,
                   static_argnames=("delta", "window", "blk", "interpret"))
def mithril_pairwise(ts: jax.Array, cnt: jax.Array, valid: jax.Array,
                     delta: int, window: int, blk: int = 128,
                     interpret=None) -> jax.Array:
    """Drop-in for core.mining.pairwise_codes ((N,S),(N,),(N,) -> (N,W)).

    Pads rows so (a) shifted window slices stay in range and (b) the row
    count tiles by ``blk``; padded rows are invalid and can never match.
    """
    n, s = ts.shape
    blk, _, pad_total = _mine_padding(n, window, blk)
    big = jnp.int32(2_000_000_000)
    ts_p = jnp.full((pad_total, s), big, jnp.int32).at[:n].set(ts)
    cnt_p = jnp.zeros((pad_total, 1), jnp.int32).at[:n, 0].set(cnt)
    val_p = jnp.zeros((pad_total, 1), jnp.int32).at[:n, 0].set(
        valid.astype(jnp.int32))
    out = pairwise_codes_kernel(ts_p, cnt_p, val_p, delta, window, blk=blk,
                                interpret=default_interpret(interpret))
    return out[:n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def mithril_record_fused(states, blocks: jax.Array, enabled: jax.Array,
                         interpret=None):
    """Fused per-request record path over a lanes axis (DESIGN.md §11).

    Drop-in for ``vmap(core.mithril.record_event)``: ``states`` is a
    stacked ``MithrilState`` with a leading ``(B,)`` lanes axis,
    ``blocks``/``enabled`` are ``(B,)``. One kernel launch covers the
    locate probe, the recording-table stamp and the mining-table insert
    for every lane; prefetch-table leaves and mining counters pass
    through untouched (``record_event`` never writes them). The tables
    go to the kernel lane-dense (buckets and mining rows minor). The
    mining table ``mine_ts`` is held ``(S, Nm)`` in the state, the
    kernel's own orientation, and passes through as it is; the
    recording tables are transposed here. The sweep engine selects
    this on TPU via ``sweep._batched_record_fn`` and runs the pure-jnp
    scatter form elsewhere — bit-identical either way
    (``tests/test_record_kernel.py``).
    """
    lanes, nb, _ = states.rec_key.shape
    i32 = jnp.int32
    blocks = blocks.astype(i32).reshape(lanes)
    enabled = jnp.broadcast_to(jnp.asarray(enabled), (lanes,)).astype(i32)

    def dense(x):           # (L, NB, W) -> (L, W, NB)
        return jnp.swapaxes(x, 1, 2)

    outs = record_step_kernel(
        blocks, enabled, bucket_of(blocks, nb), states.mine_fill,
        states.ts, dense(states.rec_key),
        jnp.transpose(states.rec_ts, (0, 2, 3, 1)),
        dense(states.rec_cnt), dense(states.rec_age),
        dense(states.rec_loc), dense(states.rec_row),
        states.mine_block[:, None], states.mine_ts,
        states.mine_cnt[:, None], interpret=default_interpret(interpret))
    (rec_key, rec_ts, rec_cnt, rec_age, rec_loc, rec_row,
     mine_block, mine_ts, mine_cnt, fill) = outs
    return states._replace(
        rec_key=dense(rec_key),
        rec_ts=jnp.transpose(rec_ts, (0, 3, 1, 2)),
        rec_cnt=dense(rec_cnt), rec_age=dense(rec_age),
        rec_loc=dense(rec_loc), rec_row=dense(rec_row),
        mine_block=mine_block[:, 0], mine_ts=mine_ts,
        mine_cnt=mine_cnt[:, 0], mine_fill=fill[:, 0, 0],
        ts=states.ts + enabled)


@functools.partial(jax.jit, static_argnames=("interpret",))
def prefetch_lookup(queries: jax.Array, pf_key: jax.Array,
                    pf_vals: jax.Array, interpret=None) -> jax.Array:
    """Batched MITHRIL prefetch-table probe: (Q,) -> (Q, P) candidates.
    The tables go to the kernel lane-dense (buckets minor)."""
    nb = pf_key.shape[0]
    queries = queries.astype(jnp.int32)
    out = hash_lookup_kernel(queries, bucket_of(queries, nb),
                             jnp.swapaxes(pf_key, 0, 1),
                             jnp.transpose(pf_vals, (1, 2, 0)),
                             interpret=default_interpret(interpret))
    return out[:, :, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                 page_table: jax.Array, lengths: jax.Array,
                 interpret=None) -> jax.Array:
    """Flash-decode over paged KV: (B,Hq,hd) x pools -> (B,Hq,hd)."""
    return paged_decode_kernel(q, k_pool, v_pool, page_table, lengths,
                               interpret=default_interpret(interpret))
