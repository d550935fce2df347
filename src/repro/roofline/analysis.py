"""Roofline analysis with while-loop trip-count correction (DESIGN.md §5).

``cost_analysis()`` on a compiled SPMD module reports PER-DEVICE flops /
bytes, and counts every while-loop body ONCE (verified empirically). All
model loops here have statically known trip counts, so we correct:

    true = measured_full                      # outer ops + each body once
         + sum_g (reps_g - 1) * probe_g       # layer-group bodies
         + attention tile extras (analytic)   # fori inside the bodies
         + chunk-scan extras (analytic)       # rwkv inter-chunk carry

``probe_g`` is the group's unit body compiled standalone UNDER THE SAME
MESH/SHARDINGS (value_and_grad of the remat'd body for train — this
reproduces the recompute + backward exactly). Collective bytes get the
same correction from the probes' HLO text.

Hardware model (TPU v5e): 197 TF/s bf16, 819 GB/s HBM, 50 GB/s/link ICI.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import SHAPES, get_config
from repro.configs.base import ModelConfig
from repro.dist import sharding as shd
from repro.dist.ctx import sharding_ctx
from repro.launch.mesh import dp_axes_of
from repro.launch.specs import cache_sds, params_sds
from repro.models import RunFlags
from repro.models.attention import block_plan
from repro.models.lm import apply_layer, layer_groups
from repro.models.rwkv6 import CHUNK as RWKV_CHUNK

PEAK_FLOPS = 197e12      # bf16 / chip (TPU v5e)
HBM_BW = 819e9           # bytes/s / chip (TPU v5e)
ICI_BW = 50e9            # bytes/s / link


# ---------------------------------------------------------------------------
# analytic attention tile accounting
# ---------------------------------------------------------------------------

def _attn_tile_counts(sq: int, skv: int, causal: bool, window: int):
    """Total executed kv-tiles across all q blocks (matches _kv_bounds)."""
    bq, bk = block_plan(sq, skv)
    n_q, n_k = sq // bq, skv // bk
    total = 0
    for qi in range(n_q):
        hi = n_k
        lo = 0
        if causal:
            hi = min(((qi + 1) * bq + bk - 1) // bk, n_k)
        if window:
            lo = max((qi * bq - window) // bk, 0)
        total += max(0, hi - lo)
    return total, n_q, bq, bk


def _attn_tile_flops(cfg: ModelConfig, b: int, bq: int, bk: int,
                     train: bool) -> float:
    """FLOPs of ONE kv tile: fwd = 2 matmuls (scores + pv); bwd adds 5."""
    h, hd = cfg.n_heads, cfg.head_dim
    one_mm = 2.0 * b * h * bq * bk * hd
    fwd = 2 * one_mm
    if not train:
        return fwd
    # remat recompute (fwd again) + bwd tiles (dv, dp, ds*k, dk = ~5 mm)
    return fwd + fwd + 5 * one_mm


def attention_extra(cfg: ModelConfig, b: int, sq: int, skv: int,
                    kind: str, n_dev: int) -> float:
    """Analytic flops of the (tiles-1) attention iterations NOT counted by
    cost_analysis, per device, summed over attention layers."""
    extra = 0.0
    for lk in cfg.pattern:
        if lk not in ("attn", "local"):
            continue
        window = cfg.window if (lk == "local" or cfg.attn_kind == "swa") else 0
        tiles, n_q, bq, bk = _attn_tile_counts(sq, skv, True, window)
        per_tile = _attn_tile_flops(cfg, b, bq, bk, kind == "train")
        # the probe/full measure counted n_q tiles (one inner iteration per
        # q-block scan step... the q-scan is also a while: counted once) —
        # conservatively assume ONE (q,kv) tile was counted per layer.
        extra += (tiles - 1) * per_tile
    if cfg.is_encoder_decoder and kind == "train":
        tiles, n_q, bq, bk = _attn_tile_counts(cfg.encoder_seq,
                                               cfg.encoder_seq, False, 0)
        per = _attn_tile_flops(cfg, b, bq, bk, True)
        extra += cfg.n_encoder_layers * (tiles - 1) * per
        # decoder cross-attention over encoder_seq
        tiles_x, _, bqx, bkx = _attn_tile_counts(sq, cfg.encoder_seq,
                                                 False, 0)
        extra += cfg.n_layers * (tiles_x - 1) * _attn_tile_flops(
            cfg, b, bqx, bkx, True)
    return extra / n_dev


def rwkv_chunk_extra(cfg: ModelConfig, b: int, s: int, kind: str,
                     n_dev: int) -> float:
    """Inter-chunk state-carry scan: (S/CHUNK - 1) uncounted iterations."""
    if "rwkv" not in cfg.pattern or s < RWKV_CHUNK:
        return 0.0
    h, hd = cfg.n_rwkv_heads, cfg.rwkv_head_size
    per_chunk = 3.0 * b * h * hd * hd          # decay*state + add kv
    mult = 4.0 if kind == "train" else 1.0
    n_chunks = s // RWKV_CHUNK
    return cfg.n_layers * (n_chunks - 1) * per_chunk * mult / n_dev


# ---------------------------------------------------------------------------
# empirical layer-group probes
# ---------------------------------------------------------------------------

def _group_probe(cfg: ModelConfig, gi: int, unit, reps, mesh, kind: str,
                 b: int, s: int, strategy: str, max_len: int = 0):
    """Lower+compile the group's unit body standalone; returns its
    cost_analysis dict and collective bytes."""
    from repro.launch.dryrun import (cost_analysis_dict,  # local import
                                     parse_collectives)  # (XLA flag)

    pall = params_sds(cfg)
    gp = pall["blocks"][gi]
    p_slice = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:],
                                                          a.dtype), gp)
    x_sds = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
    pos_sds = jax.ShapeDtypeStruct((b, s), jnp.int32)

    mode = "train" if kind == "train" else ("prefill" if kind == "prefill"
                                            else "decode")
    cache_slice = None
    if mode == "decode":
        call = cache_sds(cfg, b, max_len or SHAPES["decode_32k"].seq_len)
        # decode probes get s=1 inputs; cache slice from group gi
        centry = call[gi]
        cache_slice = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), centry)

    flags = RunFlags(remat="full" if mode == "train" else "none")

    def body(p_sl, x, positions, c_sl):
        def inner(p_and_x):
            p_, x_ = p_and_x
            xc = x_
            for j, lk in enumerate(unit):
                ce = c_sl[f"u{j}"] if c_sl is not None else None
                xc, aux, _ = apply_layer(cfg, lk, p_[f"u{j}"], xc,
                                         positions, mode, ce)
            return jnp.sum(xc.astype(jnp.float32))
        if mode == "train":
            fn = jax.checkpoint(
                inner, policy=jax.checkpoint_policies.nothing_saveable)
            val, grads = jax.value_and_grad(fn)((p_sl, x))
            return val, grads
        return inner((p_sl, x)), None

    pspec_full = shd.param_specs(pall, mesh, strategy)["blocks"][gi]
    pspec_slice = jax.tree.map(lambda sp: P(*sp[1:]), pspec_full,
                               is_leaf=lambda x: isinstance(x, P))
    psh = shd.to_named(pspec_slice, mesh)
    dp = dp_axes_of(mesh)
    dpn = dp if len(dp) > 1 else dp[0]
    dp_prod = int(np.prod([dict(zip(mesh.axis_names,
                                    mesh.devices.shape))[a] for a in dp]))
    bspec = dpn if b % dp_prod == 0 else None
    sspec = "model" if (s % 16 == 0 and s > 1) else None
    xsh = NamedSharding(mesh, P(bspec, sspec, None))
    possh = NamedSharding(mesh, P(bspec, None))
    csh = (shd.to_named(shd.cache_specs(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct((1,) + a.shape,
                                                    a.dtype), cache_slice),
        mesh), mesh) if cache_slice is not None else None)
    if csh is not None:
        csh = jax.tree.map(
            lambda sh: NamedSharding(mesh, P(*sh.spec[1:])), csh,
            is_leaf=lambda x: isinstance(x, NamedSharding))

    def wrapped(p_sl, x, positions, c_sl):
        with sharding_ctx(mesh, dp_axes=dp, tp_axis="model"):
            return body(p_sl, x, positions, c_sl)

    jfn = jax.jit(wrapped, in_shardings=(psh, xsh, possh, csh))
    with jax.set_mesh(mesh):
        compiled = jfn.lower(p_slice, x_sds, pos_sds, cache_slice).compile()
    ca = cost_analysis_dict(compiled)
    coll, _ = parse_collectives(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "collective": coll}


# ---------------------------------------------------------------------------
# assembly
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    flops_dev: float
    bytes_dev: float
    coll_dev: float
    n_dev: int
    model_flops: float

    @property
    def compute_s(self) -> float:
        return self.flops_dev / PEAK_FLOPS

    @property
    def memory_s(self) -> float:
        return self.bytes_dev / HBM_BW

    @property
    def collective_s(self) -> float:
        return self.coll_dev / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        total = self.flops_dev * self.n_dev
        return self.model_flops / total if total else 0.0

    @property
    def step_time_s(self) -> float:
        """Roofline-model step time: dominant term (others overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Useful-compute time / modeled step time (the §Perf score)."""
        ideal = self.model_flops / (self.n_dev * PEAK_FLOPS)
        return ideal / self.step_time_s if self.step_time_s else 0.0

    def to_dict(self) -> dict:
        return {**dataclasses.asdict(self),
                "compute_s": self.compute_s, "memory_s": self.memory_s,
                "collective_s": self.collective_s,
                "bottleneck": self.bottleneck,
                "useful_ratio": self.useful_ratio,
                "roofline_fraction": self.roofline_fraction}


def model_flops(cfg: ModelConfig, kind: str, batch: int, seq: int) -> float:
    n_act = cfg.param_count(active_only=True)
    tokens = batch * seq if kind != "decode" else batch
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_act * tokens


def analyze_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                 strategy: str = "fsdp", dryrun_result: Optional[dict] = None,
                 probe: bool = True) -> Roofline:
    from repro.launch.dryrun import run_cell
    from repro.launch.mesh import make_production_mesh

    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    r = dryrun_result or run_cell(arch, shape_name, multi_pod, strategy,
                                  save=False)
    if not r.get("ok"):
        raise RuntimeError(f"cell not ok: {r}")
    n_dev = r["n_devices"]
    b, s = shape.global_batch, shape.seq_len

    flops = r["flops_hlo_once"]
    bytes_ = r["bytes_hlo_once"]
    coll = float(sum(r["collective_bytes_once"].values()))

    if probe:
        mesh = make_production_mesh(multi_pod=multi_pod)
        s_eff = 1 if shape.kind == "decode" else s
        probe_strategy = ("tp_serve" if shape.kind == "decode"
                          and strategy == "fsdp" else strategy)
        for gi, (unit, reps) in enumerate(layer_groups(cfg)):
            if reps <= 1:
                continue
            pr = _group_probe(cfg, gi, unit, reps, mesh, shape.kind,
                              b, s_eff, probe_strategy, max_len=s)
            flops += (reps - 1) * pr["flops"]
            bytes_ += (reps - 1) * pr["bytes"]
            coll += (reps - 1) * sum(pr["collective"].values())

    if shape.kind != "decode":
        flops += attention_extra(cfg, b, s, s, shape.kind, n_dev)
        flops += rwkv_chunk_extra(cfg, b, s, shape.kind, n_dev)

    return Roofline(
        arch=arch, shape=shape_name, mesh=r["mesh"],
        flops_dev=flops, bytes_dev=bytes_, coll_dev=coll, n_dev=n_dev,
        model_flops=model_flops(cfg, shape.kind, b, s))


def save_roofline(rl: Roofline, out_dir: str = "results/roofline"):
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(
            out_dir, f"{rl.arch}_{rl.shape}_{rl.mesh}.json"), "w") as f:
        json.dump(rl.to_dict(), f, indent=1)


# ---------------------------------------------------------------------------
# per-kernel roofline (DESIGN.md §11)
# ---------------------------------------------------------------------------
#
# The model-level roofline above prices whole training/serving steps from
# compiled-HLO cost analysis; the per-kernel analyzer below prices the
# individual Pallas launches of the MITHRIL request path from their
# BlockSpec geometry instead. Bytes moved is the HBM<->VMEM traffic the
# BlockSpec layout implies (every block a launch reads in + writes out,
# i.e. the copy-through upper bound for aliased in-place kernels — a
# kernel can touch fewer bytes, never more). Flops counts the integer
# compare/select lattice (int ops ~ flops on the VPU). Machine peaks
# come from ``machine_peaks``: a TPU's are looked up by its
# ``device_kind`` in ``TPU_PEAKS``, and a kind missing from the table is
# an error; any other backend gets finite nominal placeholders flagged
# ``trusted=False`` so CI on CPU can still round-trip the report. They
# are never a device number. Interpreted-mode wall-clock never enters
# these numbers (DESIGN.md §11).

# (peak bf16 flop/s, HBM bytes/s) per chip, keyed by ``device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 819 GB/s HBM).
TPU_PEAKS = {"TPU v5 lite": (PEAK_FLOPS, HBM_BW)}

_NOMINAL_FLOPS = 1e12    # untrusted placeholder peaks for cpu/gpu
_NOMINAL_BW = 100e9


@dataclasses.dataclass(frozen=True)
class MachinePeaks:
    backend: str
    flops_per_s: float
    bytes_per_s: float
    trusted: bool


def machine_peaks(backend: Optional[str] = None,
                  device_kind: Optional[str] = None) -> MachinePeaks:
    """Peak flops/bandwidth for ``backend`` (default: the live backend).

    A TPU's peaks come from ``TPU_PEAKS`` by ``device_kind`` (default:
    the first live device's); a kind missing from the table raises.
    Other backends get finite nominal peaks with ``trusted=False``, so
    reports stay well-formed on CPU CI without passing as device peaks.
    """
    if backend is None:
        backend = jax.default_backend()
    if backend == "tpu":
        kind = device_kind or jax.devices()[0].device_kind
        if kind not in TPU_PEAKS:
            raise ValueError(f"no published peaks for TPU kind {kind!r}; "
                             f"known: {sorted(TPU_PEAKS)}")
        return MachinePeaks("tpu", *TPU_PEAKS[kind], True)
    return MachinePeaks(str(backend), _NOMINAL_FLOPS, _NOMINAL_BW, False)


def _record_fused_cost(g: dict):
    """One ``mithril_record_fused`` launch: per lane, the probed
    bucket's window of the recording table (``bucket_window`` buckets)
    and the whole mining table stream through VMEM once in, once out,
    plus the scalar-prefetched lane values and the fill row; compute
    is the W-way probe, R-slot stamp and S-slot insert select lattice."""
    from repro.kernels.mithril_record import LANE_TILE, bucket_window
    lanes, nb, w = g["lanes"], g["n_buckets"], g["ways"]
    r, nm, s = g["r_sup"], g["mine_rows"], g["s_sup"]
    table_words = bucket_window(nb) * w * (5 + r) + nm * (2 + s)
    bytes_ = lanes * (2 * table_words + 5 + LANE_TILE) * 4
    flops = lanes * (16 + 8 * w + 6 * r + 8 * s)
    return float(bytes_), float(flops)


def _mine_cost(g: dict):
    """One ``mithril_pairwise`` launch, which mines one lane: the sorted
    mining table in + candidate pairs out; compute is the window*S*S
    timestamp-closeness compare grid per row."""
    n, s, window = g["mine_rows"], g["s_sup"], g["window"]
    bytes_ = (n * s + 2 * n + n * window) * 4 * 2
    flops = n * window * s * 3
    return float(bytes_), float(flops)


def _hash_lookup_cost(g: dict):
    """One ``hash_lookup`` prefetch-table probe launch: per query, the
    probed bucket's window (``bucket_window`` buckets) of the keys and
    the P-wide candidate rows streams into VMEM — once in all when the
    window is the whole table, since an unchanged block is not fetched
    again — plus the query and its bucket in and the candidate list
    out; compute is the mix32 hash, the W-way compare/argmax and the
    P-wide found select per query."""
    from repro.kernels.mithril_record import bucket_window
    q, nb = g["queries"], g["n_buckets"]
    w, p = g["ways"], g["plist"]
    win = bucket_window(nb)
    fetches = 1 if win == nb else q
    bytes_ = (fetches * win * w * (1 + p) + q * (2 + p)) * 4
    flops = q * (8.0 + 4 * w + 2 * p)
    return float(bytes_), float(flops)


def _paged_decode_cost(g: dict):
    """One ``paged_decode`` step: the whole paged KV working set is
    read once (decode is bandwidth-bound), q in / o out; compute is the
    two matmuls over the gathered pages."""
    b, hq, hkv = g["batch"], g["heads_q"], g["heads_kv"]
    hd, ps, npg = g["head_dim"], g["page_size"], g["n_pages"]
    bytes_ = (2 * b * npg * ps * hkv * hd + 2 * b * hq * hd) * 4
    flops = 4.0 * b * hq * npg * ps * hd
    return float(bytes_), float(flops)


#: kernel name -> cost fn(geometry dict) -> (bytes_moved, flops).
#: Names match the ``ops``/BENCH-json kernel labels.
KERNEL_MODELS = {
    "mithril_record_fused": _record_fused_cost,
    "mithril_mine": _mine_cost,
    "hash_lookup": _hash_lookup_cost,
    "paged_decode": _paged_decode_cost,
}


@dataclasses.dataclass
class KernelRoofline:
    kernel: str
    geometry: dict
    backend: str
    bytes_moved: float
    flops: float
    peak_flops: float
    peak_bw: float
    trusted_peaks: bool

    @property
    def intensity(self) -> float:
        """Arithmetic intensity, flops per byte moved."""
        return self.flops / self.bytes_moved

    @property
    def peak_fraction(self) -> float:
        """Attainable fraction of machine peak flops at this intensity
        (1.0 when compute-bound: the memory roofline does not bind)."""
        return min(1.0, self.intensity * self.peak_bw / self.peak_flops)

    def to_dict(self) -> dict:
        return {**dataclasses.asdict(self),
                "intensity": self.intensity,
                "peak_fraction": self.peak_fraction}


def analyze_kernel(name: str, geometry: dict,
                   backend: Optional[str] = None) -> KernelRoofline:
    """Per-kernel roofline point for one launch geometry."""
    peaks = machine_peaks(backend)
    bytes_, flops = KERNEL_MODELS[name](dict(geometry))
    return KernelRoofline(
        kernel=name, geometry=dict(geometry), backend=peaks.backend,
        bytes_moved=bytes_, flops=flops,
        peak_flops=peaks.flops_per_s, peak_bw=peaks.bytes_per_s,
        trusted_peaks=peaks.trusted)
