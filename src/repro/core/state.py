"""MITHRIL state as a fixed-shape pytree (jit/scan/pjit friendly).

Layout mirrors the paper's optimized structures (Sec. 4.2):

* recording table — set-associative: storage lives in the bucket itself.
  ``rec_loc`` distinguishes in-place recording rows (0) from entries that
  migrated to the mining table (1, with ``rec_row`` the mining row), which
  replaces the paper's block->row hashmap.
* mining table — flat rows of up to S timestamps; ``mine_fill`` counts
  occupied rows; when full the mining procedure fires and clears it.
* prefetching table — set-associative, P association slots per source
  block replaced FIFO via a per-entry ring counter (the paper's shards
  become the fixed bucket array; the `M` budget maps to capacities via
  ``MithrilConfig.from_metadata_budget``).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .config import MithrilConfig
from .hashindex import EMPTY


class MithrilState(NamedTuple):
    # recording table ------------------------------------------------------
    rec_key: jax.Array    # (NB, W)  int32 block id, EMPTY if free
    rec_ts: jax.Array     # (NB, W, R) int32 timestamps
    rec_cnt: jax.Array    # (NB, W)  int32 number of recorded timestamps
    rec_age: jax.Array    # (NB, W)  int32 insertion time (FIFO eviction)
    rec_loc: jax.Array    # (NB, W)  int32 0=recording, 1=in mining table
    rec_row: jax.Array    # (NB, W)  int32 mining row when rec_loc==1
    # mining table -----------------------------------------------------------
    mine_block: jax.Array  # (Nm,)    int32
    mine_ts: jax.Array     # (S, Nm)  int32 row m's timestamps in column m
    mine_cnt: jax.Array    # (Nm,)    int32 (S+1 marks "frequent", excluded)
    mine_fill: jax.Array   # ()       int32
    # prefetching table ------------------------------------------------------
    pf_key: jax.Array     # (PB, PW)    int32 source block
    pf_vals: jax.Array    # (PB, PW, P) int32 associated blocks
    pf_cnt: jax.Array     # (PB, PW)    int32 FIFO ring position
    pf_age: jax.Array     # (PB, PW)    int32 insertion time
    # counters ----------------------------------------------------------------
    ts: jax.Array          # () int32 logical timestamp (per record event)
    n_mines: jax.Array     # () int32
    n_pairs: jax.Array     # () int32 associations written (cumulative)
    n_dropped: jax.Array   # () int32 pairs dropped by max_pairs compaction


def init_state(cfg: MithrilConfig) -> MithrilState:
    nb, w, r = cfg.rec_buckets, cfg.rec_ways, cfg.min_support
    nm, s = cfg.mine_rows, cfg.max_support
    pb, pw, p = cfg.pf_buckets, cfg.pf_ways, cfg.prefetch_list
    i32 = jnp.int32
    return MithrilState(
        rec_key=jnp.full((nb, w), EMPTY, i32),
        rec_ts=jnp.zeros((nb, w, r), i32),
        rec_cnt=jnp.zeros((nb, w), i32),
        rec_age=jnp.zeros((nb, w), i32),
        rec_loc=jnp.zeros((nb, w), i32),
        rec_row=jnp.zeros((nb, w), i32),
        mine_block=jnp.full((nm,), EMPTY, i32),
        mine_ts=jnp.zeros((s, nm), i32),
        mine_cnt=jnp.zeros((nm,), i32),
        mine_fill=jnp.zeros((), i32),
        pf_key=jnp.full((pb, pw), EMPTY, i32),
        pf_vals=jnp.full((pb, pw, p), EMPTY, i32),
        pf_cnt=jnp.zeros((pb, pw), i32),
        pf_age=jnp.zeros((pb, pw), i32),
        ts=jnp.zeros((), i32),
        n_mines=jnp.zeros((), i32),
        n_pairs=jnp.zeros((), i32),
        n_dropped=jnp.zeros((), i32),
    )
