"""Pallas TPU flash-decode kernel over a PAGED KV cache.

This is the serving hot-path that MITHRIL feeds: the tiered cache manager
(cache/tiered.py) keeps hot KV pages in HBM and prefetches predicted
pages; this kernel consumes the page table that manager maintains.

Design (TPU paged-attention shape):
* grid = (batch, n_pages); the page loop is the minor grid dim so VMEM
  scratch (running max / denominator / accumulator) carries across the
  page steps of one batch row — the flash-decode recurrence;
* page ids come from a scalar-prefetched page table; each step indexes
  one page of the head-major pool held in VMEM;
* GQA: each kv head's MXU dot covers every query row and keeps the rows
  of its own group (a row mask, no sublane slicing);
* fp32 softmax state, bf16 IO.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .backend import default_interpret

NEG_INF = -1e30


def _decode_kernel(ptab_ref, len_ref, q_ref, kpool_ref, vpool_ref, out_ref,
                   m_ref, l_ref, acc_ref, *, page_size: int, n_pages: int,
                   n_kv: int):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)               # (Hq, hd)
    hq, hd = q.shape
    g = hq // n_kv
    scale = hd ** -0.5
    page_id = ptab_ref[b, p]
    length = jnp.full((1, 1), len_ref[b], jnp.int32)

    pos = p * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, page_size), 1)
    valid = pos < length                           # (1, ps)
    # query row -> its kv head, without a vector division
    row = jax.lax.broadcasted_iota(jnp.int32, (hq, 1), 0)
    head_of = jnp.zeros_like(row)
    for h in range(1, n_kv):
        head_of = head_of + jnp.where(row >= h * g, 1, 0)

    # GQA: each kv head scores every query row, and the rows of its own
    # group are kept — full-height matmuls, no sublane slicing
    scores = jnp.zeros((hq, page_size), jnp.float32)
    for h in range(n_kv):
        k_h = kpool_ref[page_id, h].astype(jnp.float32)        # (ps, hd)
        s_h = jax.lax.dot_general(q, k_h, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        scores = jnp.where(head_of == h, s_h * scale, scores)
    scores = jnp.where(valid, scores, NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, scores.max(axis=1, keepdims=True))
    pexp = jnp.exp(scores - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + pexp.sum(axis=1, keepdims=True)

    pv = jnp.zeros((hq, hd), jnp.float32)
    for h in range(n_kv):
        v_h = vpool_ref[page_id, h].astype(jnp.float32)        # (ps, hd)
        pv_h = jax.lax.dot_general(pexp, v_h, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        pv = jnp.where(head_of == h, pv_h, pv)
    acc_new = acc_prev * corr + pv

    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(p == n_pages - 1)
    def _finish():
        out_ref[0] = (acc_new / jnp.maximum(l_new, 1e-30)
                      ).astype(out_ref.dtype)


def paged_decode_kernel(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                        page_table: jax.Array, lengths: jax.Array, *,
                        interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, Hq, hd); pools: (NP, page_size, Hkv, hd); page_table:
    (B, n_pages) int32 page ids; lengths: (B,) valid token counts.
    Returns (B, Hq, hd).

    The page table and lengths are scalar-prefetched (SMEM). The pools
    go to the kernel head-major, ``(NP, Hkv, page_size, hd)``, so one
    head of one page is a plain ``(page_size, hd)`` tile. Each pool is
    one whole-array VMEM block: the pool must fit VMEM (a known limit of
    this kernel; per-page DMA is the way past it).
    ``interpret=None``: compiled on TPU, interpreted elsewhere."""
    interpret = default_interpret(interpret)
    b, hq, hd = q.shape
    npages_total, page_size, n_kv, _ = k_pool.shape
    n_pages = page_table.shape[1]
    kernel = functools.partial(_decode_kernel, page_size=page_size,
                               n_pages=n_pages, n_kv=n_kv)
    pool_shape = (npages_total, n_kv, page_size, hd)
    pool_spec = pl.BlockSpec(pool_shape, lambda b_, p, pt, ln: (0, 0, 0, 0))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(b, n_pages),
            in_specs=[
                pl.BlockSpec((1, hq, hd), lambda b_, p, pt, ln: (b_, 0, 0)),
                pool_spec, pool_spec,
            ],
            out_specs=pl.BlockSpec((1, hq, hd),
                                   lambda b_, p, pt, ln: (b_, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hq, 1), jnp.float32),
                pltpu.VMEM((hq, 1), jnp.float32),
                pltpu.VMEM((hq, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hq, hd), q.dtype),
        interpret=interpret,
        name="paged_decode",
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32), q,
      jnp.swapaxes(k_pool, 1, 2), jnp.swapaxes(v_pool, 1, 2))
