"""Batched trace-sweep scheduler: corpus in, a handful of compiles out.

The serial ``simulate`` compiles one ``lax.scan`` per (trace, config)
pair, so sweeping a benchmark suite is compile-bound long before it is
compute-bound. This module instead

* pads a suite of traces to a common length (``pad_traces`` /
  ``repro.traces.padded_suite``),
* ``vmap``s the per-request step over the trace axis (requests at the
  same position of every trace advance together),
* scans over fixed-size time *chunks* so peak memory is bounded by
  ``chunk * n_traces`` and arbitrarily long traces stream through the
  same compiled executable,
* gates padded tails per trace so statistics are bit-identical to the
  per-trace ``simulate`` (``tests/test_sweep.py`` asserts this),
* **schedules** corpus-scale suites (``plan_sweep``/``sweep_scheduled``,
  DESIGN.md §8–§9): the cost-model lane packer sorts traces by length
  and packs them into variable-width *lane groups* drawn from a bounded
  width set — every group runs through one of at most ``max_shapes``
  compiled ``(chunk, width)`` executables (default 2), so a 135-trace
  corpus costs one or two compiles per config — and
* **shards** the lane axis across local devices
  (``dist.sharding.lane_specs`` + ``shard_map``): lanes are independent,
  so each device simulates its slice of the batch and per-lane results
  are bit-identical to the single-device path
  (``tests/test_scheduler.py`` pins this on a forced multi-device CPU).

Batching invariants (DESIGN.md §6–§7):

* the per-lane step is branchless scatter-form integer arithmetic (no
  ``lax.cond`` / ``lax.switch`` anywhere in the request path), so
  ``vmap`` lowers it to batched scatters — never to the whole-table
  select copies that cond lowering produces;
* the one expensive rare branch — the MITHRIL mining pass — is hoisted
  out of the vmapped step via the segment barriers of
  ``simulator.build_segments`` and guarded by a *batch-level*
  ``lax.cond`` (``jnp.any(need)``) around the fused
  ``mithril.mine_batched`` (one Pallas launch over all lanes on TPU), so
  it only executes when some live lane actually filled its mining table
  — callers of ``record_event`` owe that barrier before the next record
  (the record/maybe_mine contract);
* padded-tail requests carry ``valid=False`` into every segment, whose
  scatter updates then write back old values — an exhausted lane can
  neither change state, contribute to statistics, nor trigger mining.
"""

from __future__ import annotations

import collections
import functools
import queue as _queue_mod
import threading
from typing import Dict, List, Mapping, NamedTuple, Optional, Sequence, \
    Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import mithril
from .simulator import SimConfig, SimResult, Stats, build_segments

DEFAULT_CHUNK = 4096
DEFAULT_LANE_WIDTH = 16     # lanes per scheduled group (rounded to devices)
LANE_AXIS = "lanes"         # mesh axis the scheduler shards lanes over


class PaddedSuite(NamedTuple):
    names: tuple            # (B,) trace names
    blocks: np.ndarray      # (B, T) int32, zero-padded past each length
    lengths: np.ndarray     # (B,) valid request count per trace


def pad_traces(traces: Union[Mapping[str, np.ndarray],
                             Sequence[np.ndarray]]) -> PaddedSuite:
    """Stack unequal-length traces into a zero-padded (B, T) batch."""
    if isinstance(traces, Mapping):
        names = tuple(traces.keys())
        arrs = [np.asarray(t, np.int32) for t in traces.values()]
    else:
        arrs = [np.asarray(t, np.int32) for t in traces]
        names = tuple(f"trace{i:03d}" for i in range(len(arrs)))
    if not arrs:
        raise ValueError("pad_traces needs at least one trace")
    lengths = np.array([len(a) for a in arrs], np.int64)
    blocks = np.zeros((len(arrs), int(lengths.max())), np.int32)
    for i, a in enumerate(arrs):
        blocks[i, : len(a)] = a
    return PaddedSuite(names, blocks, lengths)


def _batched_pairwise_fn():
    """Pairwise-check implementations for the batched mining barrier.

    Returns ``(batched_fn, serial_fn)`` for ``mithril.mine_batched``: on
    TPU the lanes-axis Pallas kernel covers every mining lane with one
    launch (grid over (lane, row-block) — DESIGN.md §7) and the
    row-block kernel serves the single-flagged-lane fast path; elsewhere
    the pure-jnp oracles are faster than interpreted kernels, so
    ``(None, None)`` defers to ``mine_batched``'s defaults. Kernel and
    oracle are bit-identical (``tests/test_kernels.py``).
    """
    from repro.kernels.backend import on_tpu
    if not on_tpu():
        return None, None
    from repro.kernels.ops import mithril_pairwise, mithril_pairwise_batched
    return mithril_pairwise_batched, mithril_pairwise


def _batched_record_fn():
    """Record-event implementation for the vmapped request path.

    Same dispatch shape as :func:`_batched_pairwise_fn`: on TPU the
    fused record kernel (``kernels.mithril_record_fused`` — locate
    probe + circular-buffer stamp + mining-table insert in ONE launch
    per request slab, DESIGN.md §11) replaces the eleven per-table XLA
    scatters; elsewhere ``None`` defers to
    ``mithril.record_event_batched``'s default — the vmapped pure-jnp
    scatter form, which beats interpreted kernels. Kernel and scatter
    form are bit-identical (``tests/test_record_kernel.py``).
    """
    from repro.kernels.backend import on_tpu
    if not on_tpu():
        return None
    from repro.kernels.ops import mithril_record_fused
    return mithril_record_fused


def build_batched_step(cfg: SimConfig):
    """Returns (init_batched, step) for a scan over (chunk, B) request slabs.

    ``step(carry, (blocks, valid))`` advances every trace lane by one
    request: the branchless scatter-form segments run under ``vmap``,
    each mining barrier runs one batch-level ``lax.cond`` around the
    fused ``mithril.mine_batched``, and invalid (padded) lanes keep
    their previous carry bit-for-bit.
    """
    init_carry, segments = build_segments(cfg)
    mine_rows = cfg.mithril.mine_rows
    pairwise_fn, serial_pairwise_fn = (
        _batched_pairwise_fn() if cfg.use_mithril else (None, None))
    record_fn = _batched_record_fn() if cfg.use_mithril else None

    def init_batched(batch_size: int):
        return jax.vmap(lambda _: init_carry())(jnp.arange(batch_size))

    def batched_maybe_mine(mith, valid):
        """Mine exactly the lanes whose table filled this step.

        This runs at batch level — *outside* vmap — so the outer
        ``lax.cond`` is a real runtime conditional: on the (rare)
        triggering steps, ``mithril.mine_batched`` runs one fused
        association search over ALL lanes (one Pallas launch on TPU)
        and folds pairs in with vmapped scatter updates; lanes with
        ``need=False`` select their previous state bit-for-bit. On every
        other step the barrier costs one predicate reduction.
        """
        need = (mith.mine_fill >= mine_rows) & valid
        return lax.cond(
            jnp.any(need),
            lambda m: mithril.mine_batched(
                cfg.mithril, m, need, pairwise_fn=pairwise_fn,
                serial_pairwise_fn=serial_pairwise_fn),
            lambda m: m, mith)

    def step(carry, xs):
        block, valid = xs
        # padded tails: aux["valid"] gates every state write at source
        # (scatter-form no-ops), so ended lanes keep their carry with no
        # carry-wide select — the old whole-table copy per step
        new, aux = carry, {"valid": valid}
        for fn, mine_after in segments:
            gate = getattr(fn, "record_gate", None)
            if gate is not None:
                # pure recording segment: route through the batched
                # record path (fused Pallas kernel on TPU, identical
                # vmapped scatter form elsewhere) instead of vmapping
                # the segment closure
                blk, en = gate(block, aux)
                new = {**new, "mith": mithril.record_event_batched(
                    cfg.mithril, new["mith"], blk, en,
                    fused_fn=record_fn)}
            else:
                new, aux = jax.vmap(fn)(new, block, aux)
            if mine_after:
                new = {**new,
                       "mith": batched_maybe_mine(new["mith"], valid)}
        return new, aux["hit"]

    return init_batched, step


def _lane_shards(n_lanes: int, shard: Optional[bool]) -> int:
    """Devices to shard the lane axis over (1 = single-device path).

    ``shard=None`` (auto) shards over every local device when the lane
    count divides — the divisibility contract of ``dist.sharding`` —
    and runs single-device otherwise. ``shard=True`` demands the
    sharded path: a lane count that does not divide over several
    devices raises instead of quietly running on one. ``shard=False``
    forces the single-device path (the bit-exactness reference).
    """
    if shard is False:
        return 1
    n_dev = jax.local_device_count()
    if n_dev == 1 or n_lanes % n_dev == 0:
        return n_dev
    if shard:
        raise ValueError(f"shard=True: {n_lanes} lanes do not divide over "
                         f"{n_dev} devices")
    return 1


def lane_mesh(n_shards: int):
    """1-D ``lanes`` mesh over the first ``n_shards`` local devices."""
    from repro.launch.mesh import auto_mesh
    return auto_mesh((n_shards,), (LANE_AXIS,),
                     devices=jax.local_devices()[:n_shards])


@functools.lru_cache(maxsize=None)
def _runner(cfg: SimConfig, unroll: int, n_shards: int = 1):
    """One (init, jitted chunk-scan, place) triple per (config, shards).

    With ``n_shards > 1`` the chunk scan runs under ``shard_map`` on a
    1-D ``lanes`` mesh over the local devices: the carry (every leaf has
    a leading lane dim — ``dist.sharding.lane_specs``) and the
    ``(chunk, B)`` request slabs split over the lane axis, and each
    device scans its own lanes. Lanes never communicate — the mining
    barrier's ``lax.cond`` becomes a per-device conditional over the
    device's own lanes — so per-lane results are bit-identical to the
    single-device runner.
    """
    init_batched, step = build_batched_step(cfg)

    def scan_chunk(carry, blocks, valid):
        return lax.scan(step, carry, (blocks, valid), unroll=unroll)

    if n_shards <= 1:
        return init_batched, jax.jit(scan_chunk), lambda carry: carry

    # lazy: pulling repro.dist at module import would drag the model
    # stack into every cache-layer import
    from jax.sharding import PartitionSpec as P
    from repro.dist import sharding as dist_sharding

    mesh = lane_mesh(n_shards)

    def place(carry):
        """Pre-shard the initial carry so the first chunk's input
        shardings match every later chunk's (one executable, not an
        unsharded-first-call variant + a sharded steady state). Trailing
        ``None`` entries are trimmed because the executable cache keys on
        the exact spec tuple and jit-output shardings come back trimmed —
        a full-rank first call would compile a second, equivalent
        executable."""
        def trim(sp):
            entries = tuple(sp)
            while entries and entries[-1] is None:
                entries = entries[:-1]
            return P(*entries)

        specs = jax.tree.map(trim,
                             dist_sharding.lane_specs(carry, mesh,
                                                      axis=LANE_AXIS),
                             is_leaf=lambda x: isinstance(x, P))
        return jax.device_put(carry, dist_sharding.to_named(specs, mesh))

    @jax.jit
    def run_chunk(carry, blocks, valid):
        cspec = dist_sharding.lane_specs(carry, mesh, axis=LANE_AXIS)
        # (chunk, W) slabs are lane-LAST (ring_specs): the time axis
        # stays whole on every device, the lane axis splits — the same
        # layout the streaming ring buffer stages, so recycled lanes
        # keep their shard across admissions
        bspec, vspec = dist_sharding.ring_specs((blocks, valid), mesh,
                                                axis=LANE_AXIS)
        return jax.shard_map(scan_chunk, mesh=mesh,
                             in_specs=(cspec, bspec, vspec),
                             out_specs=(cspec, bspec),
                             check_vma=False)(carry, blocks, valid)

    return init_batched, run_chunk, place


def compile_count(cfg: SimConfig, unroll: int = 1, n_shards: int = 1) -> int:
    """Compiled-executable count for ``cfg``'s chunk runner.

    All chunks are padded to one (chunk, B) shape, so a full sweep — and
    every later sweep with the same batch geometry — reports 1.
    """
    return int(_runner(cfg, unroll, n_shards)[1]._cache_size())


def reset_runners() -> None:
    """Drop cached compiled runners (test isolation for compile counts)."""
    _runner.cache_clear()


class SweepResult(NamedTuple):
    stats: Stats            # stacked: every leaf has a leading (B,) axis
    hit_curve: np.ndarray   # (B, T) bool, False past each trace's length
    lengths: np.ndarray     # (B,)
    compiles: int           # NEW compiles this sweep caused (0 = all cached)
    seconds: float          # wall-clock for this sweep call

    @property
    def n_traces(self) -> int:
        return len(self.lengths)

    def result(self, i: int) -> SimResult:
        """Per-trace view, same type the serial ``simulate`` returns."""
        stats = Stats(*(np.asarray(leaf)[i] for leaf in self.stats))
        return SimResult(stats, self.hit_curve[i, : int(self.lengths[i])])

    def hit_ratios(self) -> np.ndarray:
        req = np.maximum(np.asarray(self.stats.requests), 1)
        return np.asarray(self.stats.hits) / req

    def precisions(self, src: int) -> np.ndarray:
        issued = np.asarray(self.stats.pf_issued)[:, src].astype(np.float64)
        used = np.asarray(self.stats.pf_used)[:, src]
        with np.errstate(invalid="ignore", divide="ignore"):
            return np.where(issued > 0, used / issued, np.nan)


def sweep(cfg: SimConfig, blocks: np.ndarray,
          lengths: Optional[np.ndarray] = None,
          chunk: int = DEFAULT_CHUNK, unroll: int = 1,
          shard: Optional[bool] = None) -> SweepResult:
    """Run a (B, T) padded trace batch through one configuration.

    This is the OFFLINE SPECIAL CASE of the streaming ingestion engine
    (:func:`sweep_streaming`, DESIGN.md §10): every trace is submitted
    at virtual step 0 on its own lane (``lane_width = B``), so the
    scheduler admits the whole batch into the first slab, no lane ever
    recycles, and the staged slabs are exactly the ``(chunk, B)``
    transposes of the padded block matrix — the same compiled
    executable, carry evolution and results as the pre-streaming
    chunk loop, bit for bit.

    ``lengths`` gives each trace's valid prefix (default: full T).
    Requests past a trace's length are bit-exact no-ops excluded from
    all statistics (source-gated, DESIGN.md §6). Time is padded up to a
    chunk multiple so every chunk has the same shape — one compilation
    serves the whole stream. Results are bit-identical to running each
    trace through ``simulate`` serially; the record/maybe_mine contract
    (``core.mithril``) is honored internally via the batch-level mining
    barriers of ``build_batched_step`` — callers never interleave their
    own recording with a sweep's.

    ``shard`` selects the device layout: ``None``/``True`` shard the
    lane axis over all local devices whenever the batch width divides
    (per-lane results stay bit-identical — lanes are independent);
    ``False`` forces the single-device runner.
    """
    import time

    t0 = time.time()
    blocks = np.ascontiguousarray(np.asarray(blocks, np.int32))
    if blocks.ndim != 2:
        raise ValueError(f"blocks must be (B, T), got {blocks.shape}")
    n_traces, n_req = blocks.shape
    lengths = (np.full((n_traces,), n_req, np.int64) if lengths is None
               else np.asarray(lengths, np.int64))
    if lengths.shape != (n_traces,) or (lengths > n_req).any() \
            or (lengths < 0).any():
        raise ValueError("lengths must be (B,) within [0, trace axis]")

    stream = sweep_streaming(cfg, blocks, lengths=lengths,
                             lane_width=n_traces, chunk=chunk,
                             unroll=unroll, shard=shard)
    res = stream.result
    return SweepResult(stats=res.stats, hit_curve=res.hit_curve,
                       lengths=lengths, compiles=res.compiles,
                       seconds=time.time() - t0)


# ---------------------------------------------------------------------------
# Corpus-scale scheduler: cost-model lane packer, bounded compile shapes
# ---------------------------------------------------------------------------

DEFAULT_MAX_SHAPES = 2      # distinct lane widths (= compiled slab shapes)
# Per-group serial-dispatch cost in lane-equivalents. Any positive value
# stops the pure padded-steps objective from shredding the corpus into
# width-1 groups (grouping equal-padded traces then always wins); the
# default is deliberately small because a chunk launch costs far less
# than one lane of chunk compute — raise it on hardware where narrow
# lanes underfill the vector unit (DESIGN.md §9).
DEFAULT_PACK_OVERHEAD = 0.25


class LaneGroup(NamedTuple):
    indices: Tuple[int, ...]    # original trace positions in this group
    padded_t: int               # group time axis (a chunk multiple)
    lane_width: int             # lanes this group pads to
    chunk: int                  # time-axis chunk of this group's slabs


class SweepPlan(NamedTuple):
    """Device-and-shape schedule for a heterogeneous trace corpus.

    Groups are consecutive runs of the length-sorted corpus (longest
    first), each running through a ``(chunk, width)`` slab shape drawn
    from at most ``max_shapes`` distinct shapes — one compiled
    executable per shape. Both axes are free per group: a short-trace
    group may take a *narrower lane width* AND a *finer time chunk*
    than the primary shape (the second-chunk freedom of DESIGN.md §9),
    so chunk granularity no longer floors the padded tail on short
    corpora. Widths are always multiples of ``n_shards`` so the lane
    axis divides the device mesh; chunks are halvings of the base
    chunk. ``lane_width``/``chunk`` are the widest group's shape (the
    primary slab).
    """

    groups: Tuple[LaneGroup, ...]
    lane_width: int             # max group width (primary compiled shape)
    chunk: int                  # base (primary) time chunk
    n_shards: int
    total_requests: int         # sum of valid per-trace lengths
    fixed_lane_steps: int       # padded_lane_steps of the fixed-shape plan

    @property
    def padded_lane_steps(self) -> int:
        """Total (lane x request) slots the schedule executes."""
        return sum(g.padded_t * g.lane_width for g in self.groups)

    @property
    def shape_widths(self) -> Tuple[int, ...]:
        """Distinct lane widths across the compiled slab shapes."""
        return tuple(sorted({g.lane_width for g in self.groups}))

    @property
    def shapes(self) -> Tuple[Tuple[int, int], ...]:
        """Distinct compiled ``(chunk, width)`` slab shapes."""
        return tuple(sorted({(g.chunk, g.lane_width) for g in self.groups}))

    @property
    def waste_ratio(self) -> float:
        """Fraction of executed lane-steps that are padded-tail waste."""
        steps = self.padded_lane_steps
        return 1.0 - self.total_requests / steps if steps else 0.0

    @property
    def fixed_waste_ratio(self) -> float:
        """Waste ratio of the fixed-shape reference plan (same inputs)."""
        if not self.fixed_lane_steps:
            return 0.0
        return 1.0 - self.total_requests / self.fixed_lane_steps

    def packer_stats(self) -> Dict[str, object]:
        """Packer-efficiency summary recorded in BENCH json."""
        return {
            "n_traces": sum(len(g.indices) for g in self.groups),
            "n_groups": len(self.groups),
            "widths": list(self.shape_widths),
            "shapes": [f"{c}x{w}" for c, w in self.shapes],
            "n_shapes": len(self.shapes),
            "chunk": self.chunk,
            "n_shards": self.n_shards,
            "padded_lane_steps": int(self.padded_lane_steps),
            "ideal_lane_steps": int(self.total_requests),
            "waste_ratio": round(self.waste_ratio, 6),
            "fixed_padded_lane_steps": int(self.fixed_lane_steps),
            "fixed_waste_ratio": round(self.fixed_waste_ratio, 6),
            "reduction_vs_fixed": round(
                1.0 - (self.padded_lane_steps / self.fixed_lane_steps
                       if self.fixed_lane_steps else 1.0), 6),
        }


def _width_candidates(w_max: int, n_shards: int) -> Tuple[int, ...]:
    """Packer width ladder: ``w_max`` and its successive halvings, each
    rounded up to a multiple of ``n_shards`` (the §4 divisibility
    contract applied to the lane axis), deduplicated, ascending."""
    cands = set()
    w = w_max
    while w >= 1:
        cands.add(-(-w // n_shards) * n_shards)
        if w == 1:
            break
        w //= 2
    return tuple(sorted(cands))


# Chunk-ladder depth: the base chunk plus up to this many halvings are
# shape candidates. Three halvings reach chunk/8 — finer granularity
# stops mattering once the per-trace remainder is < 1/8 of a chunk,
# while the candidate-shape count (widths x chunks) stays small enough
# to enumerate shape subsets exhaustively.
_CHUNK_LADDER = 3


def _chunk_candidates(base: int) -> Tuple[int, ...]:
    """Time-axis chunk ladder: the base chunk and its halvings
    (``_CHUNK_LADDER`` deep, floored at 1), deduplicated, ascending."""
    cands = set()
    c = base
    for _ in range(_CHUNK_LADDER + 1):
        cands.add(max(1, c))
        c //= 2
    return tuple(sorted(cands))


def _padded_len(length: int, chunk: int) -> int:
    return -(-max(1, int(length)) // chunk) * chunk


def _pack(lengths: Sequence[int], shapes: Sequence[Tuple[int, int]],
          overhead: float) -> Tuple[float, Tuple[Tuple[int, int], ...]]:
    """Optimal consecutive partition of the length-sorted corpus.

    ``lengths[i]`` is trace ``i``'s raw length, sorted descending, so a
    group covering positions ``[i, i+w)`` pads its time axis to position
    ``i``'s length rounded up to the group's chunk. ``shapes`` are the
    candidate ``(width, chunk)`` slab shapes. Minimizes

        sum_g padded_t_g * (w_g + overhead)

    — the schedule's padded lane-steps plus a per-group serial-dispatch
    term (``overhead`` lane-equivalents) that keeps the otherwise
    degenerate width-1 optimum from shredding the corpus into
    per-trace groups. Returns (cost, per-group (width, chunk) in order).
    """
    n = len(lengths)
    cost = [0.0] * (n + 1)
    choice: list = [None] * n
    for i in range(n - 1, -1, -1):
        best, best_s = None, shapes[0]
        for w, ck in shapes:
            c = _padded_len(lengths[i], ck) * (w + overhead) \
                + cost[min(n, i + w)]
            if best is None or c < best:
                best, best_s = c, (w, ck)
        cost[i], choice[i] = best, best_s
    group_shapes = []
    i = 0
    while i < n:
        group_shapes.append(choice[i])
        i += choice[i][0]
    return cost[0], tuple(group_shapes)


def plan_sweep(lengths, lane_width: Optional[int] = None,
               chunk: int = DEFAULT_CHUNK,
               n_shards: Optional[int] = None,
               max_shapes: int = DEFAULT_MAX_SHAPES,
               overhead_lanes: float = DEFAULT_PACK_OVERHEAD) -> SweepPlan:
    """Pack traces into lane groups with a cost-model packer (§9).

    Traces sort longest-first; groups are consecutive runs of that
    order, so a group's time axis pads to its FIRST member's length
    rounded up to the *group's* chunk. The packer chooses per-group
    ``(width, chunk)`` slab shapes from the candidate ladders — widths
    are ``lane_width`` (default ``min(n, DEFAULT_LANE_WIDTH)``) and its
    halvings rounded up to ``n_shards`` multiples; chunks are the base
    chunk and its halvings — to minimize total padded lane-steps plus
    an ``overhead_lanes`` serial-dispatch term per group, subject to
    the compile budget: at most ``max_shapes`` DISTINCT ``(chunk,
    width)`` shapes, because every distinct slab shape is one more
    executable. A short-trace group may therefore take a finer time
    chunk than the primary shape (not just a narrower width), which
    recovers the chunk-floor waste on short corpora. Plans are
    guaranteed never worse than the fixed-shape reference (single
    shape ``(lane_width, chunk)``) in padded lane-steps — when the
    cost-model pick loses on pure padded waste it falls back to the
    reference (``fixed_lane_steps`` records the reference either way).

    ``n_shards=None`` reads the local device count; pass 1 to plan a
    single-device schedule. The effective base chunk is capped at the
    longest trace (padded up), so each group's scan reuses its shape's
    ``(chunk, width)`` slab.
    """
    lengths = np.asarray(lengths, np.int64)
    n = len(lengths)
    if n == 0:
        raise ValueError("plan_sweep needs at least one trace")
    if max_shapes < 1:
        raise ValueError("max_shapes must be >= 1")
    if n_shards is None:
        n_shards = max(1, jax.local_device_count())
    w_max = min(n, DEFAULT_LANE_WIDTH) if lane_width is None \
        else max(1, lane_width)
    w_max = -(-w_max // n_shards) * n_shards
    eff_chunk = max(1, min(chunk, int(lengths.max())))
    order = np.argsort(-lengths, kind="stable")   # longest first
    sorted_lens = [int(lengths[i]) for i in order]

    def steps_of(group_shapes: Sequence[Tuple[int, int]]) -> int:
        total, i = 0, 0
        for w, ck in group_shapes:
            total += _padded_len(sorted_lens[i], ck) * w
            i += w
        return total

    # fixed-shape reference: the single-shape plan at (w_max, eff_chunk)
    _, fixed_shapes = _pack(sorted_lens, ((w_max, eff_chunk),),
                            overhead_lanes)
    fixed_steps = steps_of(fixed_shapes)

    # shape subsets within the compile budget, simplest-first: every
    # single shape, then pairs, ... — ties keep the earlier (simpler)
    # plan, so the search is deterministic. Candidate shapes are the
    # width ladder x chunk ladder, ordered coarse-to-fine.
    from itertools import combinations
    cands = [(w, ck)
             for w in reversed(_width_candidates(w_max, n_shards))
             for ck in reversed(_chunk_candidates(eff_chunk))]
    best_cost, best_shapes = None, fixed_shapes
    for size in range(1, min(max_shapes, len(cands)) + 1):
        for subset in combinations(cands, size):
            cost, shapes = _pack(sorted_lens, subset, overhead_lanes)
            if best_cost is None or cost < best_cost:
                best_cost, best_shapes = cost, shapes

    # never-worse guard: the packer must not trade padded waste for
    # dispatch savings relative to the documented fixed-shape reference
    if steps_of(best_shapes) > fixed_steps:
        best_shapes = fixed_shapes

    groups, i = [], 0
    for w, ck in best_shapes:
        idx = order[i: i + w]
        groups.append(LaneGroup(tuple(int(j) for j in idx),
                                _padded_len(sorted_lens[i], ck),
                                int(w), int(ck)))
        i += w
    return SweepPlan(tuple(groups),
                     max(g.lane_width for g in groups),
                     eff_chunk, n_shards,
                     int(lengths.sum()), int(fixed_steps))


def sweep_scheduled(cfg: SimConfig,
                    traces: Union[Mapping[str, np.ndarray],
                                  Sequence[np.ndarray], PaddedSuite,
                                  np.ndarray],
                    lengths: Optional[np.ndarray] = None,
                    lane_width: Optional[int] = None,
                    chunk: int = DEFAULT_CHUNK, unroll: int = 1,
                    shard: Optional[bool] = None,
                    plan: Optional[SweepPlan] = None) -> SweepResult:
    """Sweep an arbitrary-size trace corpus through one configuration.

    Accepts a dict/sequence of unequal-length traces, a
    :class:`PaddedSuite`, or a ``(B, T)`` block array with ``lengths``.
    The corpus is scheduled with :func:`plan_sweep` (the cost-model lane
    packer, §9), each group runs through :func:`sweep` — sharded over
    local devices when possible — and per-trace results are reassembled
    in the ORIGINAL trace order. Statistics are bit-identical to
    sweeping (or serially simulating) each trace alone; the whole corpus
    costs at most ``max_shapes`` compiles per config because groups draw
    their ``(chunk, width)`` slab geometry from the packer's bounded
    shape set. Groups holding fewer traces than their lane width are
    padded with empty (length-0) lanes, which are bit-exact no-ops under
    the §6 masking contract.
    """
    import time

    t0 = time.time()
    if not isinstance(traces, np.ndarray):
        # suite-like inputs carry their own lengths; a conflicting
        # explicit lengths argument would be silently wrong either way
        if lengths is not None:
            raise ValueError("pass lengths only with a (B, T) block array"
                             " — suites already carry per-trace lengths")
        if not isinstance(traces, PaddedSuite):
            traces = pad_traces(traces)
        blocks, lengths = traces.blocks, traces.lengths
    else:
        blocks = np.asarray(traces, np.int32)
    if blocks.ndim != 2:
        raise ValueError(f"traces must stack to (B, T), got {blocks.shape}")
    n, t_max = blocks.shape
    lengths = (np.full((n,), t_max, np.int64) if lengths is None
               else np.asarray(lengths, np.int64))
    if lengths.shape != (n,) or (lengths > t_max).any() \
            or (lengths < 0).any():
        raise ValueError("lengths must be (B,) within [0, trace axis]")

    if plan is None:
        plan = plan_sweep(lengths, lane_width, chunk,
                          n_shards=1 if shard is False else None)

    stats_out = None
    hit = np.zeros((n, t_max), bool)
    compiles = 0
    for g in plan.groups:
        gb = np.zeros((g.lane_width, g.padded_t), np.int32)
        gl = np.zeros((g.lane_width,), np.int64)
        for j, idx in enumerate(g.indices):
            ln = int(lengths[idx])
            gb[j, :ln] = blocks[idx, :ln]
            gl[j] = ln
        res = sweep(cfg, gb, gl, chunk=g.chunk, unroll=unroll,
                    shard=shard)
        compiles += res.compiles
        if stats_out is None:
            stats_out = [np.zeros((n,) + np.asarray(leaf).shape[1:],
                                  np.asarray(leaf).dtype)
                         for leaf in res.stats]
        for j, idx in enumerate(g.indices):
            ln = int(lengths[idx])
            hit[idx, :ln] = res.hit_curve[j, :ln]
            for leaf_out, leaf in zip(stats_out, res.stats):
                leaf_out[idx] = np.asarray(leaf)[j]

    return SweepResult(stats=Stats(*stats_out), hit_curve=hit,
                       lengths=lengths,
                       compiles=compiles,
                       seconds=time.time() - t0)


def sweep_grid(cfgs: Dict[str, SimConfig], blocks: np.ndarray,
               lengths: Optional[np.ndarray] = None,
               chunk: int = DEFAULT_CHUNK,
               unroll: int = 1) -> Dict[str, SweepResult]:
    """Sweep the trace batch through every config in the grid.

    Grid entries with *equal* configs — e.g. a parameter sweep whose
    pivot equals the baseline — share one simulation pass outright (the
    frozen configs are hashable), on top of the per-config executable
    cache in ``_runner``.
    """
    memo: Dict[SimConfig, SweepResult] = {}
    out = {}
    for name, cfg in cfgs.items():
        if cfg not in memo:
            memo[cfg] = sweep(cfg, blocks, lengths, chunk=chunk,
                              unroll=unroll)
        out[name] = memo[cfg]
    return out


# ---------------------------------------------------------------------------
# Streaming ingestion engine: ring-buffered slabs, lane recycling (§10)
# ---------------------------------------------------------------------------

DEFAULT_RING_DEPTH = 4      # slabs the producer stages ahead of the device


class _Tenant:
    """Host-side bookkeeping for one submitted trace.

    ``avail`` (optional, same length as the trace) gives each request's
    arrival step on the engine's virtual clock, nondecreasing; ``None``
    means the whole trace is available at step 0 (the offline case).
    ``cursor`` is the next unplaced request — the ONLY progress state,
    and it is host-known, which is what lets the scheduler run ahead of
    the device (see :class:`RingBuffer`).
    """

    __slots__ = ("index", "blocks", "avail", "length", "cursor")

    def __init__(self, index: int, blocks: np.ndarray,
                 avail: Optional[np.ndarray], length: int):
        self.index = index
        self.blocks = blocks
        self.avail = avail
        self.length = length
        self.cursor = 0


class _Slab(NamedTuple):
    """One staged ``(chunk, W)`` request slab plus its host-side routing.

    ``placements`` maps device outputs back to traces: for each lane
    that placed requests, ``(lane, tenant, cursor0, row0, k, positions)``
    says requests ``cursor0 .. cursor0+k-1`` of ``tenant`` sit at slab
    rows ``row0 .. row0+k-1`` when ``positions`` is ``None`` (the
    contiguous fast path — offline traces always, arrival traces
    whenever the placed run has no interior gap), else at
    ``positions[0..k-1]``. ``harvest`` lists ``(tenant, lane)`` pairs
    that drain once this slab runs — the consumer snapshots those lanes'
    statistics from the post-slab carry (device arrays are immutable,
    so the snapshot is a free reference, not a copy). ``buffers`` holds
    the host staging pair so the async drain can recycle it into the
    producer's pool once the slab's outputs materialize (``None`` on
    the synchronous path, where staging arrays are throwaway).
    """

    blocks: jax.Array                       # (chunk, W) int32, staged
    valid: jax.Array                        # (chunk, W) bool, staged
    reset: Optional[np.ndarray]             # (W,) bool; None = no admission
    placements: Tuple[Tuple[int, int, int, int, int,
                            Optional[np.ndarray]], ...]
    harvest: Tuple[Tuple[int, int], ...]
    buffers: Optional[Tuple[np.ndarray, np.ndarray]] = None


class RingBuffer:
    """Thread-safe bounded FIFO ring of staged request slabs.

    The producer (the host scheduler, its own thread under
    ``async_producer=True``) stages up to ``depth`` slabs ahead of the
    consumer (the device chunk scan): host marshalling and H2D staging
    of slabs k+1..k+depth overlap slab k's compute. Admission and
    placement depend only on host-known cursors — never on device
    results — which is what makes the produce-ahead legal; the depth
    bounds in-flight device memory at ``depth * chunk * W`` request
    slots.

    ``push``/``pop`` default to the non-blocking semantics the
    synchronous engine uses (full push / empty pop raise a clear
    ``RuntimeError``); ``block=True`` waits on a condition variable
    instead and counts each wait in the stall telemetry: a producer
    that blocked on a full ring bumps ``push_stalls`` (device is the
    bottleneck), a consumer that blocked on an empty ring bumps
    ``pop_stalls`` (host marshalling is the bottleneck). ``close()``
    wakes every waiter; a blocking pop on a closed, drained ring
    returns ``None`` (end of stream).
    """

    def __init__(self, depth: int = DEFAULT_RING_DEPTH):
        if isinstance(depth, bool) or not isinstance(
                depth, (int, np.integer)) or depth < 1:
            raise ValueError(f"ring depth must be an int >= 1, "
                             f"got {depth!r}")
        self.depth = int(depth)
        self._q: collections.deque = collections.deque()
        self._cv = threading.Condition()
        self._closed = False
        self.push_stalls = 0    # producer waited on a full ring
        self.pop_stalls = 0     # consumer waited on an empty ring

    def __len__(self) -> int:
        return len(self._q)

    @property
    def full(self) -> bool:
        return len(self._q) >= self.depth

    @property
    def empty(self) -> bool:
        return not self._q

    @property
    def closed(self) -> bool:
        return self._closed

    def close(self) -> None:
        """End of stream: wake all waiters; further pushes are errors."""
        with self._cv:
            self._closed = True
            self._cv.notify_all()

    def push(self, slab: _Slab, block: bool = False) -> None:
        with self._cv:
            if len(self._q) >= self.depth:
                if not block:
                    raise RuntimeError(
                        "ring buffer full — pop before pushing")
                self.push_stalls += 1
                while len(self._q) >= self.depth and not self._closed:
                    self._cv.wait()
            if self._closed:
                raise RuntimeError("ring buffer closed")
            self._q.append(slab)
            self._cv.notify_all()

    def pop(self, block: bool = False) -> Optional[_Slab]:
        with self._cv:
            if not self._q:
                if not block:
                    raise RuntimeError(
                        "ring buffer empty — push (produce) before popping")
                if not self._closed:
                    self.pop_stalls += 1
                    while not self._q and not self._closed:
                        self._cv.wait()
            if not self._q:
                return None         # closed and fully drained
            slab = self._q.popleft()
            self._cv.notify_all()
            return slab


@jax.jit
def _masked_reset(carry, template, mask):
    """Lane recycling: where ``mask`` is set, the lane's carry becomes
    the init template bit for bit; every other lane keeps its state
    untouched. A recycled lane is therefore indistinguishable from a
    fresh lane in a fresh batch — the §6 lane-independence argument
    reduces streaming bit-identity to this one equality."""
    def leaf(c, t):
        m = mask.reshape((mask.shape[0],) + (1,) * (c.ndim - 1))
        return jnp.where(m, t, c)

    return jax.tree.map(leaf, carry, template)


class StreamResult(NamedTuple):
    """Streaming-engine result plus schedule telemetry.

    ``result`` carries per-trace statistics in SUBMISSION order — the
    same :class:`SweepResult` type the offline engines return, and per
    trace bit-identical to them (lane assignment, slab chunking and
    arrival gaps are all invisible under the §6 masking contract).
    ``lane_steps`` is the executed (lane x request) slot count — the
    recycling analogue of ``SweepPlan.padded_lane_steps``.

    ``pipeline`` carries the producer-pipeline telemetry: stage-busy
    seconds (``produce_s`` host marshalling + H2D staging,
    ``consume_s`` reset + chunk-scan dispatch, ``drain_s`` D2H
    materialization + hit-curve scatter), the loop wall clock
    ``wall_s``, the ring-buffer stall counters (``producer_stalls`` =
    producer blocked on a full ring, ``consumer_stalls`` = consumer
    blocked on an empty ring) and ``overlap`` = ``1 - wall / sum of
    stage-busy`` clipped to [0, 1] — 0 when the stages serialize,
    approaching ``1 - 1/n_stages`` when they fully overlap. Timings
    and stalls are scheduling noise (WARN-gated in
    ``benchmarks.compare``); every other ``streaming_stats`` key is
    deterministic and FAIL-gated.
    """

    result: SweepResult
    lane_width: int
    chunk: int
    n_slabs: int
    async_producer: bool = True
    pipeline: Optional[Dict[str, object]] = None
    n_shards: int = 1           # devices the lane axis was split over

    @property
    def lane_steps(self) -> int:
        return self.n_slabs * self.chunk * self.lane_width

    def streaming_stats(self) -> Dict[str, object]:
        """Schedule-efficiency summary recorded in BENCH json."""
        total = int(np.asarray(self.result.lengths).sum())
        steps = self.lane_steps
        stats: Dict[str, object] = {
            "lane_width": self.lane_width,
            "chunk": self.chunk,
            "n_slabs": self.n_slabs,
            "lane_steps": int(steps),
            "ideal_lane_steps": total,
            "waste_ratio": round(1.0 - total / steps, 6) if steps else 0.0,
            "async_producer": bool(self.async_producer),
        }
        if self.pipeline is not None:
            stats["pipeline"] = dict(self.pipeline)
        return stats


def sweep_streaming(cfg: SimConfig,
                    traces: Union[Mapping[str, np.ndarray],
                                  Sequence[np.ndarray], PaddedSuite,
                                  np.ndarray],
                    lengths: Optional[np.ndarray] = None,
                    arrivals: Optional[Sequence[np.ndarray]] = None,
                    lane_width: Optional[int] = None,
                    chunk: int = DEFAULT_CHUNK, unroll: int = 1,
                    shard: Optional[bool] = None,
                    ring_depth: int = DEFAULT_RING_DEPTH,
                    async_producer: bool = True) -> StreamResult:
    """Online ingestion: arrival is the primitive, traces stream through
    a recycled lane pool (DESIGN.md §10).

    The engine keeps ``lane_width`` device lanes and a virtual step
    clock that advances one ``chunk`` per slab. A host scheduler admits
    queued traces (FIFO) into idle lanes at slab boundaries, places each
    admitted trace's arrived requests into its lane's slab column
    (arrival gaps become ``valid=False`` no-op rows), and RECYCLES a
    lane the moment its trace drains — the next queued trace is admitted
    mid-run after a masked init reset (:func:`_masked_reset`) instead of
    the engine scanning padded tails. Slabs stage through a
    :class:`RingBuffer` ``ring_depth`` ahead of the device.

    ``arrivals`` gives per-trace nondecreasing request arrival steps
    (``None`` = everything at step 0); when every trace arrives at 0 and
    ``lane_width`` covers the batch this degrades exactly to
    :func:`sweep` — which is, in fact, implemented on top of this
    engine. Statistics and hit curves are bit-identical to the offline
    engines per trace: lanes are independent and invalid slots are
    bit-exact no-ops (§6), and the batch-level mining barrier masks
    per-lane ``need`` (§7), so neither lane assignment, chunk phase,
    arrival gaps nor pool composition can leak between traces
    (``tests/test_streaming.py`` pins this).

    ``async_producer=True`` (the default) runs the host scheduler on a
    background thread: slab marshalling into a recycled pool of
    preallocated staging buffers plus non-blocking ``jax.device_put``
    H2D uploads overlap the device chunk scan, and a drain thread
    materializes each slab's hit rows off-device as they complete (so
    host memory stays bounded and D2H overlaps compute). Production
    order depends only on host-known cursors, so the async pipeline is
    bit-identical to the synchronous fallback (``async_producer=False``
    — the legacy produce/consume loop, pinned by
    ``tests/test_async_pipeline.py``). Stage timings, ring stall
    counters and the overlap ratio surface in
    :meth:`StreamResult.streaming_stats` under ``"pipeline"``.
    """
    import time

    t0 = time.time()
    if isinstance(async_producer, np.bool_):
        async_producer = bool(async_producer)
    if not isinstance(async_producer, bool):
        raise ValueError(f"async_producer must be a bool, "
                         f"got {async_producer!r}")
    if isinstance(ring_depth, bool) or not isinstance(
            ring_depth, (int, np.integer)) or ring_depth < 1:
        raise ValueError(f"ring_depth must be an int >= 1, "
                         f"got {ring_depth!r}")
    ring_depth = int(ring_depth)
    if not isinstance(traces, np.ndarray):
        if lengths is not None:
            raise ValueError("pass lengths only with a (B, T) block array"
                             " — suites already carry per-trace lengths")
        if not isinstance(traces, PaddedSuite):
            traces = pad_traces(traces)
        blocks, lengths = traces.blocks, traces.lengths
    else:
        blocks = np.asarray(traces, np.int32)
    if blocks.ndim != 2:
        raise ValueError(f"traces must stack to (B, T), got {blocks.shape}")
    n, t_max = blocks.shape
    lengths = (np.full((n,), t_max, np.int64) if lengths is None
               else np.asarray(lengths, np.int64))
    if lengths.shape != (n,) or (lengths > t_max).any() \
            or (lengths < 0).any():
        raise ValueError("lengths must be (B,) within [0, trace axis]")

    avails: List[Optional[np.ndarray]] = [None] * n
    if arrivals is not None:
        if len(arrivals) != n:
            raise ValueError(f"arrivals must give one array per trace "
                             f"({n}), got {len(arrivals)}")
        for i, a in enumerate(arrivals):
            if a is None:
                continue
            a = np.asarray(a, np.int64)
            if a.shape != (int(lengths[i]),):
                raise ValueError(f"arrivals[{i}] must have shape "
                                 f"({int(lengths[i])},), got {a.shape}")
            if a.size and ((np.diff(a) < 0).any() or a[0] < 0):
                raise ValueError(f"arrivals[{i}] must be nondecreasing "
                                 "and nonnegative")
            avails[i] = a

    w = min(n, DEFAULT_LANE_WIDTH) if lane_width is None \
        else max(1, int(lane_width))
    n_shards = _lane_shards(w, shard)
    chunk = max(1, min(int(chunk), max(1, t_max)))
    tenants = [_Tenant(i, blocks[i], avails[i], int(lengths[i]))
               for i in range(n)]

    init_batched, run_chunk, place = _runner(cfg, unroll, n_shards)
    before = compile_count(cfg, unroll, n_shards)
    template = place(init_batched(w))
    carry = template
    if n_shards > 1:
        from repro.dist import sharding as dist_sharding
        mesh = lane_mesh(n_shards)

        def place_mask(m):
            spec = dist_sharding.occupancy_specs(m, mesh, axis=LANE_AXIS)
            return jax.device_put(m, dist_sharding.to_named(spec, mesh))
    else:
        place_mask = jnp.asarray

    queue: collections.deque = collections.deque(range(n))
    lanes: List[Optional[int]] = [None] * w
    clock = 0
    # tenant -> (stats pytree reference, lane) snapshotted at drain time
    stash: List[Optional[Tuple[Stats, int]]] = [None] * n

    # --- staging: how host slab arrays become device arrays ------------
    # Sync keeps the legacy throwaway jnp.asarray staging bit for bit.
    # Async marshals into a recycled pool of preallocated buffer pairs
    # (the drain recycles a pair only after the slab's outputs
    # materialize — by then the chunk scan has consumed the upload, so
    # reuse is safe even if the CPU backend aliased the host buffer)
    # and uploads with non-blocking jax.device_put: plain on one device
    # (same avals + default sharding as jnp.asarray, so no extra
    # executable), pre-sharded per ring_specs on a mesh (ring_put) so
    # the shard_map consumer skips the dispatch-time reshard.
    if async_producer:
        pool: _queue_mod.Queue = _queue_mod.Queue()
        for _ in range(ring_depth + 3):
            pool.put((np.zeros((chunk, w), np.int32),
                      np.zeros((chunk, w), bool)))

        def alloc():
            b, v = pool.get()
            b.fill(0)
            v.fill(False)
            return b, v

        if n_shards > 1:
            def stage(b, v):
                return dist_sharding.ring_put((b, v), mesh, axis=LANE_AXIS)
        else:
            def stage(b, v):
                return jax.device_put((b, v))
    else:
        def alloc():
            return (np.zeros((chunk, w), np.int32),
                    np.zeros((chunk, w), bool))

        def stage(b, v):
            return jnp.asarray(b), jnp.asarray(v)

    timers = {"produce_s": 0.0, "consume_s": 0.0, "drain_s": 0.0}

    def produce() -> Optional[_Slab]:
        nonlocal clock
        tp = time.perf_counter()
        while True:
            t_start = clock
            reset = np.zeros((w,), bool)
            for lane in range(w):
                if lanes[lane] is not None:
                    continue
                # zero-length submissions drain at admission: init stats,
                # no lane occupied (bit-identical to an all-masked lane)
                while queue and tenants[queue[0]].length == 0:
                    stash[queue.popleft()] = (template["stats"], 0)
                if not queue:
                    break
                head = tenants[queue[0]]
                first = 0 if head.avail is None \
                    else int(head.avail[head.cursor])
                if first < t_start + chunk:
                    queue.popleft()
                    lanes[lane] = head.index
                    reset[lane] = True
                else:
                    break       # FIFO: a not-yet-arrived head blocks
            if any(la is not None for la in lanes):
                break
            if not queue:
                timers["produce_s"] += time.perf_counter() - tp
                return None     # fully drained
            # every lane idle, nothing arrived yet: fast-forward the
            # clock to the slab containing the head's first arrival
            head = tenants[queue[0]]
            clock = (int(head.avail[head.cursor]) // chunk) * chunk
        slab_blocks, slab_valid = alloc()
        placements, harvest = [], []
        for lane, ti in enumerate(lanes):
            if ti is None:
                continue
            t = tenants[ti]
            cap = min(t.length - t.cursor, chunk)
            if t.avail is None:
                # offline lanes always place a gapless run from row 0:
                # contiguous slice writes, no index vectors built
                row0, k, pos = 0, cap, None
            else:
                # request k lands at slab row k + the running max of its
                # arrival slack: in-order placement, one row per request,
                # never before arrival — gaps stay valid=False no-ops
                slack = (t.avail[t.cursor: t.cursor + cap] - t_start
                         - np.arange(cap))
                p = np.arange(cap) + np.maximum(
                    np.maximum.accumulate(slack, axis=0)
                    if cap else slack, 0)
                p = p[p < chunk]
                k = len(p)
                if k and int(p[-1]) - int(p[0]) + 1 == k:
                    # no interior gap: same contiguous fast path
                    row0, pos = int(p[0]), None
                else:
                    row0, pos = 0, p
            if k:
                if pos is None:
                    slab_blocks[row0: row0 + k, lane] = \
                        t.blocks[t.cursor: t.cursor + k]
                    slab_valid[row0: row0 + k, lane] = True
                else:
                    slab_blocks[pos, lane] = t.blocks[t.cursor: t.cursor + k]
                    slab_valid[pos, lane] = True
                placements.append((lane, ti, t.cursor, row0, k, pos))
                t.cursor += k
            if t.cursor == t.length:
                harvest.append((ti, lane))
                lanes[lane] = None      # recycled at the next admission
        clock = t_start + chunk
        dev_blocks, dev_valid = stage(slab_blocks, slab_valid)
        timers["produce_s"] += time.perf_counter() - tp
        return _Slab(dev_blocks, dev_valid,
                     reset if reset.any() else None,
                     tuple(placements), tuple(harvest),
                     (slab_blocks, slab_valid) if async_producer else None)

    hit_curve = np.zeros((n, t_max), bool)

    def scatter_hits(hits, placements) -> None:
        h = np.asarray(hits)                    # (chunk, W); blocks on
        for lane, ti, c0, row0, k, pos in placements:   # device results
            if pos is None:
                hit_curve[ti, c0: c0 + k] = h[row0: row0 + k, lane]
            else:
                hit_curve[ti, c0: c0 + k] = h[pos, lane]

    ring = RingBuffer(ring_depth)
    n_slabs, first_slab = 0, True
    t_wall = time.perf_counter()

    if async_producer:
        # three-stage pipeline: producer thread marshals + stages,
        # the calling thread dispatches the chunk scans in ring order
        # (same order the sync loop runs them — bit-identity is by
        # construction), a drain thread materializes hit rows as each
        # slab's compute completes and recycles its staging buffers
        prod_err: List[BaseException] = []
        drain_err: List[BaseException] = []
        drain_q: _queue_mod.Queue = _queue_mod.Queue(maxsize=ring_depth + 2)

        def producer_main():
            try:
                while True:
                    slab = produce()
                    if slab is None:
                        break
                    ring.push(slab, block=True)
            except BaseException as e:  # noqa: BLE001 — surfaced below
                prod_err.append(e)
            finally:
                ring.close()

        def drain_main():
            while True:
                item = drain_q.get()
                if item is None:
                    return
                hits, placements, bufs = item
                td = time.perf_counter()
                try:
                    if not drain_err:
                        scatter_hits(hits, placements)
                except BaseException as e:  # noqa: BLE001
                    drain_err.append(e)     # keep draining: never block
                finally:                    # the consumer on a dead drain
                    timers["drain_s"] += time.perf_counter() - td
                    if bufs is not None:
                        pool.put(bufs)

        producer = threading.Thread(target=producer_main, daemon=True,
                                    name="sweep-producer")
        drainer = threading.Thread(target=drain_main, daemon=True,
                                   name="sweep-drain")
        producer.start()
        drainer.start()
        try:
            while True:
                slab = ring.pop(block=True)
                if slab is None:
                    break
                tc = time.perf_counter()
                # slab 0 skips the reset outright: carry IS the template
                if slab.reset is not None and not first_slab:
                    carry = _masked_reset(carry, template,
                                          place_mask(slab.reset))
                first_slab = False
                carry, hits = run_chunk(carry, slab.blocks, slab.valid)
                for ti, lane in slab.harvest:
                    stash[ti] = (carry["stats"], lane)
                n_slabs += 1
                timers["consume_s"] += time.perf_counter() - tc
                drain_q.put((hits, slab.placements, slab.buffers))
        finally:
            ring.close()        # unblocks a producer stuck mid-push
            drain_q.put(None)
            drainer.join()
            producer.join()
        if prod_err:
            raise prod_err[0]
        if drain_err:
            raise drain_err[0]
    else:
        # synchronous fallback: the legacy single-thread loop — fill the
        # ring, run one slab, materialize every hit record at the end
        hit_records: List[Tuple[jax.Array, Tuple]] = []
        producing = True
        while True:
            while producing and not ring.full:
                slab = produce()
                if slab is None:
                    producing = False
                    break
                ring.push(slab)
            if ring.empty:
                break
            slab = ring.pop()
            tc = time.perf_counter()
            # slab 0 skips the reset outright: the carry IS the template
            if slab.reset is not None and not first_slab:
                carry = _masked_reset(carry, template,
                                      place_mask(slab.reset))
            first_slab = False
            carry, hits = run_chunk(carry, slab.blocks, slab.valid)
            hit_records.append((hits, slab.placements))
            for ti, lane in slab.harvest:
                stash[ti] = (carry["stats"], lane)
            n_slabs += 1
            timers["consume_s"] += time.perf_counter() - tc

        # materialize: everything device-side resolved once, at the end
        td = time.perf_counter()
        for hits, placements in hit_records:
            scatter_hits(hits, placements)
        timers["drain_s"] += time.perf_counter() - td

    wall_s = time.perf_counter() - t_wall
    mat: Dict[int, list] = {}
    rows = []
    for ti in range(n):
        st, lane = stash[ti]
        if id(st) not in mat:
            mat[id(st)] = [np.asarray(leaf) for leaf in st]
        rows.append([leaf[lane] for leaf in mat[id(st)]])
    stats = Stats(*(np.stack([r[j] for r in rows])
                    for j in range(len(Stats._fields))))

    busy = timers["produce_s"] + timers["consume_s"] + timers["drain_s"]
    pipeline = {
        "produce_s": round(timers["produce_s"], 4),
        "consume_s": round(timers["consume_s"], 4),
        "drain_s": round(timers["drain_s"], 4),
        "wall_s": round(wall_s, 4),
        "producer_stalls": int(ring.push_stalls),
        "consumer_stalls": int(ring.pop_stalls),
        "overlap": round(max(0.0, 1.0 - wall_s / busy), 4) if busy else 0.0,
    }
    after = compile_count(cfg, unroll, n_shards)
    result = SweepResult(stats=stats, hit_curve=hit_curve, lengths=lengths,
                         compiles=after - before,
                         seconds=time.time() - t0)
    return StreamResult(result=result, lane_width=w, chunk=chunk,
                        n_slabs=n_slabs, async_producer=async_producer,
                        pipeline=pipeline, n_shards=n_shards)
