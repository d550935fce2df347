"""Batched trace-sweep engine: corpus in, one compile per config out.

The serial ``simulate`` compiles one ``lax.scan`` per (trace, config)
pair, so sweeping a benchmark suite is compile-bound long before it is
compute-bound. Its one engine, ``sweep_streaming`` (DESIGN.md §6, §10;
``sweep`` is its ``(B, T)`` convenience),

* takes a suite as a mapping or sequence of traces, a
  :class:`PaddedSuite` or a zero-padded ``(B, T)`` block array with
  ``lengths``, normalised and checked once (``_suite_arrays``),
* ``vmap``s the per-request step over a pool of lanes, each holding one
  trace at a time (requests at the same slab row of every lane advance
  together),
* scans over fixed-size time *chunks* so peak memory is bounded by
  ``chunk * lane_width`` and arbitrarily long traces stream through the
  same compiled executable,
* **recycles** a lane whose trace drains: the next queued trace takes
  it at the following slab after a masked reset, so every slab has one
  ``(chunk, lane_width)`` shape and a corpus of any lengths costs one
  compile per config,
* gates padded rows per lane so statistics are bit-identical to the
  per-trace ``simulate`` (``tests/test_sweep.py`` asserts this), and
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
  ``lax.cond`` (``jnp.any(need)``) around ``mithril.mine_batched``,
  which mines the flagged lanes one at a time (one Pallas launch per
  lane on TPU), so it only executes when some live lane actually filled
  its mining table — callers of ``record_event`` owe that barrier
  before the next record (the record/maybe_mine contract);
* padded-tail requests carry ``valid=False`` into every segment, whose
  scatter updates then write back old values — an exhausted lane can
  neither change state, contribute to statistics, nor trigger mining.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import queue as _queue_mod
import threading
import time
from typing import Dict, List, Mapping, NamedTuple, Optional, Sequence, \
    Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.profiler import TraceAnnotation

from repro.core import mithril
from .simulator import (SimConfig, SimResult, Stats, build_segments,
                        write_flags)

DEFAULT_CHUNK = 4096
DEFAULT_LANE_WIDTH = 16     # lanes in the streaming engine's pool
LANE_AXIS = "lanes"         # mesh axis the engine shards lanes over


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


def _suite_arrays(traces, lengths: Optional[np.ndarray]
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """A sweep's input as ``(blocks (B, T) int32, lengths (B,) int64)``.

    ``traces`` is a mapping or sequence of unequal-length traces, a
    :class:`PaddedSuite`, or a ``(B, T)`` block array; only the array
    takes ``lengths`` (default: full T), because a suite carries its
    own. Raises ``ValueError`` on a conflicting ``lengths``, a block
    array that is not 2-D, or a length outside ``[0, T]``.
    """
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
    return blocks, lengths


def _batched_pairwise_fn():
    """Pairwise-check implementation for the batched mining barrier.

    Returns the per-lane ``pairwise_fn`` for ``mithril.mine_batched``,
    which mines the flagged lanes one at a time: on TPU the row-block
    Pallas kernel (``kernels.ops.mithril_pairwise``); elsewhere the
    pure-jnp oracle is faster than an interpreted kernel, so ``None``
    defers to ``mine_batched``'s default. Kernel and oracle are
    bit-identical (``tests/test_kernels.py``).
    """
    from repro.kernels.backend import on_tpu
    if not on_tpu():
        return None
    from repro.kernels.ops import mithril_pairwise
    return mithril_pairwise


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


# Columns of the carry's per-lane ``mine_passes`` counters: passes in
# which the lane was mined alone, multi-lane passes (steps on which the
# barrier mined several lanes) it took part in, and multi-lane passes it
# led (the first lane mined in the pass), so the last column sums to the
# multi-lane passes, one per device that ran one.
MINE_PASS_KEYS = ("solo_passes", "fused_lanes", "fused_passes")


def build_batched_step(cfg: SimConfig):
    """Returns (init_batched, step) for a scan over (chunk, B) request slabs.

    ``step(carry, (blocks, valid))`` (in a write-back configuration
    ``(blocks, valid, writes)``) advances every trace lane by one
    request: the branchless scatter-form segments run under ``vmap``,
    each mining barrier runs one batch-level ``lax.cond`` around
    ``mithril.mine_batched``'s loop over the flagged lanes, and invalid
    (padded) lanes keep their previous carry bit-for-bit.

    Each part of the step runs under a ``jax.named_scope`` — ``access``,
    ``record``, ``barrier``, ``prefetch`` (MITHRIL's lookup and inserts),
    ``amp``, ``pg``, from the segment's name, with a write-back cache's
    dirty bookkeeping under ``writeback`` inside ``access`` and the
    prefetch inserts — so XLA labels every operation, and a device trace
    splits the step by part. With MITHRIL
    on, the carry holds per-lane ``mine_passes`` counters
    (``MINE_PASS_KEYS``) that the barrier bumps.
    """
    init_carry, segments = build_segments(cfg)
    mine_rows = cfg.mithril.mine_rows
    pairwise_fn = _batched_pairwise_fn() if cfg.use_mithril else None
    record_fn = _batched_record_fn() if cfg.use_mithril else None

    def init_lane(_):
        carry = init_carry()
        if cfg.use_mithril:
            carry["mine_passes"] = jnp.zeros((len(MINE_PASS_KEYS),),
                                             jnp.int32)
        return carry

    def init_batched(batch_size: int):
        return jax.vmap(init_lane)(jnp.arange(batch_size))

    def batched_maybe_mine(carry, valid):
        """Mine exactly the lanes whose table filled this step.

        This runs at batch level — *outside* vmap — so the outer
        ``lax.cond`` is a real runtime conditional: on the (rare)
        triggering steps, ``mithril.mine_batched`` mines the flagged
        lanes one at a time, each with the serial association search
        (one Pallas launch per lane on TPU) and a fold into that lane's
        own tables; lanes with ``need=False`` are never touched. On
        every other step the barrier costs one predicate reduction.
        The counters say whether a lane was mined alone on its step or
        beside others (a multi-lane pass).
        """
        mith = carry["mith"]
        need = (mith.mine_fill >= mine_rows) & valid
        mith = lax.cond(
            jnp.any(need),
            lambda m: mithril.mine_batched(
                cfg.mithril, m, need, pairwise_fn=pairwise_fn),
            lambda m: m, mith)
        n = jnp.sum(need.astype(jnp.int32))
        fused = need & (n > 1)
        lead = fused & (jnp.arange(need.shape[0]) == jnp.argmax(need))
        passes = jnp.stack([need & (n == 1), fused, lead], axis=1)
        return {**carry, "mith": mith,
                "mine_passes": carry["mine_passes"]
                + passes.astype(jnp.int32)}

    def step(carry, xs):
        block, valid, *write = xs
        # padded tails: aux["valid"] gates every state write at source
        # (scatter-form no-ops), so ended lanes keep their carry with no
        # carry-wide select — the old whole-table copy per step
        new, aux = carry, {"valid": valid}
        if cfg.write_back:
            aux["write"] = write[0]
        for fn, mine_after in segments:
            gate = getattr(fn, "record_gate", None)
            if gate is not None:
                # pure recording segment: route through the batched
                # record path (fused Pallas kernel on TPU, identical
                # vmapped scatter form elsewhere) instead of vmapping
                # the segment closure
                with jax.named_scope("record"):
                    blk, en = gate(block, aux)
                    new = {**new, "mith": mithril.record_event_batched(
                        cfg.mithril, new["mith"], blk, en,
                        fused_fn=record_fn)}
            else:
                with jax.named_scope(fn.__name__.removeprefix("seg_")):
                    new, aux = jax.vmap(fn)(new, block, aux)
            if mine_after:
                with jax.named_scope("barrier"):
                    new = batched_maybe_mine(new, valid)
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

    if cfg.write_back:
        # the write column is a third slab, staged only where it is read
        def scan_chunk(carry, blocks, valid, writes):
            return lax.scan(step, carry, (blocks, valid, writes),
                            unroll=unroll)
    else:
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
    def run_chunk(carry, *slabs):
        cspec = dist_sharding.lane_specs(carry, mesh, axis=LANE_AXIS)
        # (chunk, W) slabs are lane-LAST (ring_specs): the time axis
        # stays whole on every device, the lane axis splits — the same
        # layout the streaming ring buffer stages, so recycled lanes
        # keep their shard across admissions
        sspecs = dist_sharding.ring_specs(slabs, mesh, axis=LANE_AXIS)
        return jax.shard_map(scan_chunk, mesh=mesh,
                             in_specs=(cspec, *sspecs),
                             out_specs=(cspec, sspecs[0]),
                             check_vma=False)(carry, *slabs)

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
        stats = jax.tree.map(lambda leaf: np.asarray(leaf)[i], self.stats)
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

    The offline special case of :func:`sweep_streaming` (DESIGN.md
    §10): every trace is submitted at virtual step 0 on its own lane
    (``lane_width = B``), so the whole batch is admitted into the first
    slab and no lane ever recycles.

    ``lengths`` gives each trace's valid prefix (default: full T).
    Requests past a trace's length are bit-exact no-ops excluded from
    all statistics (source-gated, DESIGN.md §6). Results are
    bit-identical to running each trace through ``simulate`` serially.
    ``shard`` selects the device layout: ``None``/``True`` shard the
    lane axis over all local devices whenever the batch width divides
    (per-lane results stay bit-identical — lanes are independent);
    ``False`` forces the single-device runner. A write-back
    configuration is refused: its write flags go through
    :func:`sweep_streaming`'s ``writes``.
    """
    if cfg.write_back:
        raise ValueError("sweep runs read-only caches; a write-back "
                         "configuration runs through sweep_streaming")
    return sweep_streaming(cfg, blocks, lengths=lengths,
                           lane_width=len(blocks), chunk=chunk,
                           unroll=unroll, shard=shard).result


# ---------------------------------------------------------------------------
# Streaming ingestion engine: ring-buffered slabs, lane recycling (§10)
# ---------------------------------------------------------------------------

DEFAULT_RING_DEPTH = 4      # slabs the producer stages ahead of the device


@contextlib.contextmanager
def _span(name: str, timers: Optional[Dict[str, float]] = None,
          key: Optional[str] = None, **args):
    """A host span on the profiler's clock: a ``TraceAnnotation`` named
    ``name``, carrying ``args`` (a slab's index), whose seconds are also
    added to ``timers[key]`` when ``key`` is given. The engine's stage
    timings and its profiler spans are the same intervals."""
    t = time.perf_counter()
    try:
        with TraceAnnotation(name, **args):
            yield
    finally:
        if key is not None:
            timers[key] += time.perf_counter() - t


class _Tenant:
    """Host-side bookkeeping for one submitted trace.

    ``avail`` (optional, same length as the trace) gives each request's
    arrival step on the engine's virtual clock, nondecreasing; ``None``
    means the whole trace is available at step 0 (the offline case).
    ``cursor`` is the next unplaced request — the ONLY progress state,
    and it is host-known, which is what lets the scheduler run ahead of
    the device (see :class:`RingBuffer`). ``writes`` holds each request's
    write flag in a write-back configuration, else ``None``.
    """

    __slots__ = ("index", "blocks", "avail", "length", "cursor", "writes")

    def __init__(self, index: int, blocks: np.ndarray,
                 avail: Optional[np.ndarray], length: int,
                 writes: Optional[np.ndarray] = None):
        self.index = index
        self.blocks = blocks
        self.avail = avail
        self.length = length
        self.cursor = 0
        self.writes = writes


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
    ``writes`` is the staged write column of a write-back configuration
    (``None`` where the configuration reads only, and nothing is staged).
    """

    blocks: jax.Array                       # (chunk, W) int32, staged
    valid: jax.Array                        # (chunk, W) bool, staged
    reset: Optional[np.ndarray]             # (W,) bool; None = no admission
    placements: Tuple[Tuple[int, int, int, int, int,
                            Optional[np.ndarray]], ...]
    harvest: Tuple[Tuple[int, int], ...]
    buffers: Optional[Tuple[np.ndarray, ...]] = None
    writes: Optional[jax.Array] = None      # (chunk, W) bool, staged

    @property
    def slabs(self) -> tuple:
        """The chunk runner's slab arguments."""
        return ((self.blocks, self.valid) if self.writes is None
                else (self.blocks, self.valid, self.writes))


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
    same :class:`SweepResult` type :func:`sweep` returns, and per
    trace bit-identical to the serial ``simulate`` (lane assignment, slab chunking and
    arrival gaps are all invisible under the §6 masking contract).
    ``lane_steps`` is the executed (lane x request) slot count.

    ``pipeline`` carries the producer-pipeline telemetry: stage-busy
    seconds (``produce_s`` admission, marshalling and the H2D staging
    call, ``consume_s`` reset + chunk-scan dispatch, ``drain_s`` D2H
    materialization + hit-curve scatter), ``staging_wait_s`` (the
    producer idle, waiting for a free staging buffer; not busy), the
    loop wall clock ``wall_s``, the ring-buffer stall counters
    (``producer_stalls`` = producer blocked on a full ring,
    ``consumer_stalls`` = consumer blocked on an empty ring) and
    ``overlap`` = ``1 - wall / sum of stage-busy`` clipped to [0, 1] — 0
    when the stages serialize, approaching ``1 - 1/n_stages`` when they
    fully overlap. Each timing is the sum of the engine's ``sweep.*``
    profiler spans of that stage. Timings and stalls are scheduling
    noise (WARN-gated in ``benchmarks.compare``); every other
    ``streaming_stats`` key is deterministic and FAIL-gated.

    ``mine_passes`` (MITHRIL configurations only) holds each trace's
    mining passes, in submission order, by the columns of
    ``MINE_PASS_KEYS``: passes that mined its lane alone, multi-lane
    passes that mined it, and multi-lane passes it led. A trace's solo
    passes plus its multi-lane passes are the passes the serial
    ``simulate`` mines it with; which kind a pass is depends on how
    many lanes of a device fill their mining tables on the same step.
    """

    result: SweepResult
    lane_width: int
    chunk: int
    n_slabs: int
    async_producer: bool = True
    pipeline: Optional[Dict[str, object]] = None
    n_shards: int = 1           # devices the lane axis was split over
    mine_passes: Optional[np.ndarray] = None    # (n, 3) int64

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
        if self.mine_passes is not None:
            stats["mining"] = dict(zip(
                MINE_PASS_KEYS, map(int, self.mine_passes.sum(axis=0))))
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
                    async_producer: bool = True,
                    writes: Optional[Sequence[np.ndarray]] = None
                    ) -> StreamResult:
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

    ``writes`` gives a write-back configuration (``SimConfig.write_back``)
    each trace's per-request write flags, one bool array per trace in
    the style of ``arrivals`` (``None`` = reads); the producer places
    them beside the blocks and stages them as a third slab.

    ``arrivals`` gives per-trace nondecreasing request arrival steps
    (``None`` = everything at step 0); when every trace arrives at 0 and
    ``lane_width`` covers the batch this is :func:`sweep`, the offline
    special case. Statistics and hit curves are bit-identical to the
    serial ``simulate`` per trace: lanes are independent and invalid slots are
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
    :meth:`StreamResult.streaming_stats` under ``"pipeline"``, and the
    mining passes by path under ``"mining"``.

    ``pipeline["staged_bytes"]`` counts the bytes the producer put to the
    device: every staged slab array, the write column included.

    Each stage runs under a ``jax.profiler.TraceAnnotation`` — per slab
    ``sweep.staging_wait``, ``sweep.produce`` (holding ``sweep.stage``),
    ``sweep.reset``, ``sweep.dispatch`` and ``sweep.drain``, each with
    the slab's index as ``slab``; once a call ``sweep.setup`` and
    ``sweep.harvest`` — so a profiler trace names what the host was
    doing on the device's clock.
    """
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
    blocks, lengths = _suite_arrays(traces, lengths)
    n, t_max = blocks.shape

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
    if writes is not None and len(writes) != n:
        raise ValueError(f"writes must give one array per trace "
                         f"({n}), got {len(writes)}")

    w = min(n, DEFAULT_LANE_WIDTH) if lane_width is None \
        else max(1, int(lane_width))
    n_shards = _lane_shards(w, shard)
    chunk = max(1, min(int(chunk), max(1, t_max)))
    wb = cfg.write_back
    tenants = [_Tenant(i, blocks[i], avails[i], int(lengths[i]),
                       write_flags(cfg, None if writes is None else writes[i],
                                   int(lengths[i]))
                       if wb or writes is not None else None)
               for i in range(n)]

    with _span("sweep.setup"):
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
    # tenant -> (stats pytree, mining counters, lane) references,
    # snapshotted at drain time
    stash: List[Optional[Tuple[Stats, Optional[jax.Array], int]]] = [None] * n

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
    def new_buffers():
        """One slab's host arrays: blocks, valid and, in a write-back
        configuration, the write column."""
        return ((np.zeros((chunk, w), np.int32), np.zeros((chunk, w), bool))
                + ((np.zeros((chunk, w), bool),) if wb else ()))

    if async_producer:
        pool: _queue_mod.Queue = _queue_mod.Queue()
        for _ in range(ring_depth + 3):
            pool.put(new_buffers())

        if n_shards > 1:
            def stage(*arrs):
                return dist_sharding.ring_put(arrs, mesh, axis=LANE_AXIS)
        else:
            def stage(*arrs):
                return jax.device_put(arrs)
    else:
        def stage(*arrs):
            return tuple(jnp.asarray(a) for a in arrs)

    timers = {"produce_s": 0.0, "staging_wait_s": 0.0, "consume_s": 0.0,
              "drain_s": 0.0}
    made = 0            # slabs produced, so the next slab's index
    staged_bytes = 0    # what the producer put to the device

    def produce(bufs: Tuple[np.ndarray, ...]) -> Optional[_Slab]:
        """The next slab, marshalled into ``bufs`` and staged; ``None``
        once every trace is placed."""
        with _span("sweep.produce", timers, "produce_s", slab=made):
            return _produce(bufs)

    def _produce(bufs) -> Optional[_Slab]:
        nonlocal clock, made, staged_bytes
        while True:
            t_start = clock
            reset = np.zeros((w,), bool)
            for lane in range(w):
                if lanes[lane] is not None:
                    continue
                # zero-length submissions drain at admission: init stats,
                # no lane occupied (bit-identical to an all-masked lane)
                while queue and tenants[queue[0]].length == 0:
                    stash[queue.popleft()] = (template["stats"],
                                              template.get("mine_passes"), 0)
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
                return None     # fully drained
            # every lane idle, nothing arrived yet: fast-forward the
            # clock to the slab containing the head's first arrival
            head = tenants[queue[0]]
            clock = (int(head.avail[head.cursor]) // chunk) * chunk
        slab_blocks, slab_valid, *slab_writes = bufs
        for arr in bufs:
            arr.fill(0)
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
                rows = slice(row0, row0 + k) if pos is None else pos
                slab_blocks[rows, lane] = t.blocks[t.cursor: t.cursor + k]
                slab_valid[rows, lane] = True
                if wb:
                    slab_writes[0][rows, lane] = \
                        t.writes[t.cursor: t.cursor + k]
                placements.append((lane, ti, t.cursor, row0, k, pos))
                t.cursor += k
            if t.cursor == t.length:
                harvest.append((ti, lane))
                lanes[lane] = None      # recycled at the next admission
        clock = t_start + chunk
        with _span("sweep.stage", slab=made):
            dev = stage(*bufs)
        staged_bytes += sum(arr.nbytes for arr in bufs)
        made += 1
        return _Slab(dev[0], dev[1],
                     reset if reset.any() else None,
                     tuple(placements), tuple(harvest),
                     bufs if async_producer else None,
                     dev[2] if wb else None)

    hit_curve = np.zeros((n, t_max), bool)

    def drain(hits, placements, index: int) -> None:
        """Scatter slab ``index``'s hit rows into the hit curve."""
        with _span("sweep.drain", timers, "drain_s", slab=index):
            h = np.asarray(hits)                # (chunk, W); blocks on
            for lane, ti, c0, row0, k, pos in placements:  # the device
                if pos is None:
                    hit_curve[ti, c0: c0 + k] = h[row0: row0 + k, lane]
                else:
                    hit_curve[ti, c0: c0 + k] = h[pos, lane]

    def consume(slab: _Slab, index: int):
        """Reset slab ``index``'s admitted lanes, dispatch its chunk scan
        and snapshot the lanes that drain with it; returns its hit rows."""
        nonlocal carry
        # slab 0 skips the reset outright: the carry IS the template
        if slab.reset is not None and index:
            with _span("sweep.reset", timers, "consume_s", slab=index):
                carry = _masked_reset(carry, template,
                                      place_mask(slab.reset))
        with _span("sweep.dispatch", timers, "consume_s", slab=index):
            carry, hits = run_chunk(carry, *slab.slabs)
            for ti, lane in slab.harvest:
                stash[ti] = (carry["stats"], carry.get("mine_passes"), lane)
        return hits

    ring = RingBuffer(ring_depth)
    n_slabs = 0
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
                    # the drain recycles a buffer pair only after the
                    # slab's outputs materialize — by then the chunk scan
                    # has consumed the upload, so reuse is safe
                    with _span("sweep.staging_wait", timers,
                               "staging_wait_s", slab=made):
                        bufs = pool.get()
                    slab = produce(bufs)
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
                hits, slab, k = item
                try:
                    if not drain_err:
                        drain(hits, slab.placements, k)
                except BaseException as e:  # noqa: BLE001
                    drain_err.append(e)     # keep draining: never block
                finally:                    # the consumer on a dead drain
                    pool.put(slab.buffers)

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
                hits = consume(slab, n_slabs)
                drain_q.put((hits, slab, n_slabs))
                n_slabs += 1
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
                slab = produce(new_buffers())
                if slab is None:
                    producing = False
                    break
                ring.push(slab)
            if ring.empty:
                break
            slab = ring.pop()
            hit_records.append((consume(slab, n_slabs), slab.placements))
            n_slabs += 1

        # materialize: everything device-side resolved once, at the end
        for k, (hits, placements) in enumerate(hit_records):
            drain(hits, placements, k)

    wall_s = time.perf_counter() - t_wall
    with _span("sweep.harvest"):
        mat: Dict[int, list] = {}
        rows, passes = [], []
        treedef = jax.tree.structure(template["stats"])
        for ti in range(n):
            st, mp, lane = stash[ti]
            if id(st) not in mat:
                mat[id(st)] = ([np.asarray(leaf)
                                for leaf in jax.tree.leaves(st)],
                               None if mp is None else np.asarray(mp))
            leaves, mps = mat[id(st)]
            rows.append([leaf[lane] for leaf in leaves])
            if mps is not None:
                passes.append(mps[lane])
        stats = jax.tree.unflatten(treedef, [
            np.stack([r[j] for r in rows])
            for j in range(treedef.num_leaves)])
        mine_passes = (np.stack(passes).astype(np.int64)
                       if cfg.use_mithril else None)

    busy = timers["produce_s"] + timers["consume_s"] + timers["drain_s"]
    pipeline = {
        "produce_s": round(timers["produce_s"], 4),
        "staging_wait_s": round(timers["staging_wait_s"], 4),
        "consume_s": round(timers["consume_s"], 4),
        "drain_s": round(timers["drain_s"], 4),
        "wall_s": round(wall_s, 4),
        "producer_stalls": int(ring.push_stalls),
        "consumer_stalls": int(ring.pop_stalls),
        "overlap": round(max(0.0, 1.0 - wall_s / busy), 4) if busy else 0.0,
        "staged_bytes": int(staged_bytes),
    }
    after = compile_count(cfg, unroll, n_shards)
    result = SweepResult(stats=stats, hit_curve=hit_curve, lengths=lengths,
                         compiles=after - before,
                         seconds=time.time() - t0)
    return StreamResult(result=result, lane_width=w, chunk=chunk,
                        n_slabs=n_slabs, async_producer=async_producer,
                        pipeline=pipeline, n_shards=n_shards,
                        mine_passes=mine_passes)
