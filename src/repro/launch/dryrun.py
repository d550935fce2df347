import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()
# NOTE: the two lines above MUST run before any other import (jax locks the
# device count on first initialization). Do not reorder.

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production meshes and dump memory/cost/collective analysis.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--strategy fsdp]

Outputs one JSON per cell under results/dryrun/.
"""

import argparse      # noqa: E402
import json          # noqa: E402
import re            # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import numpy as np   # noqa: E402

from repro.configs import SHAPES, all_cells, cell_enabled, get_config  # noqa: E402
from repro.launch.mesh import make_production_mesh                     # noqa: E402
from repro.launch.specs import input_specs                             # noqa: E402
from repro.launch.steps import jit_cell                                # noqa: E402
from repro.models import RunFlags                                      # noqa: E402

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _tensor_bytes(type_str: str) -> int:
    """Bytes of one HLO type string like 'bf16[8,128,4096]' (tuples summed)."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def cost_analysis_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict (empty when the backend
    reports nothing)."""
    return compiled.cost_analysis() or {}


def parse_collectives(hlo_text: str):
    """Sum output bytes of every collective op, by kind.

    Parses per-instruction lines of the (SPMD, per-device) HLO module.
    NOTES:
    * ops inside while bodies are counted once — the roofline module
      applies trip-count corrections (DESIGN.md §5);
    * TPU-equivalence adjustment: the CPU backend lowers bf16 dots as
      f32-with-converts and the partitioner hoists those converts ABOVE
      the weight all-gathers, doubling their bytes. A real TPU (native
      bf16 MXU) gathers bf16. f32 collectives fed by a convert(...) are
      therefore counted at bf16 width (flagged in the counts dict).
    """
    by_kind = {k: 0 for k in _COLLECTIVES}
    counts = {k: 0 for k in _COLLECTIVES}
    counts["f32_convert_adjusted"] = 0
    for line in hlo_text.splitlines():
        s = line.strip()
        # "%name = bf16[...] all-gather(%operand)" — op after '=' and type
        m = re.match(r"%?[\w.\-]+\s*=\s*((?:\([^)]*\)|[\w\[\],{}]+))\s+"
                     r"([\w\-]+)\(%?([\w.\-]+)", s)
        if not m:
            continue
        op = m.group(2)
        if any(op.startswith(c) for c in _COLLECTIVES):
            kind = next(c for c in _COLLECTIVES if op.startswith(c))
            nbytes = _tensor_bytes(m.group(1))
            if "f32" in m.group(1) and "convert" in m.group(3):
                nbytes //= 2
                counts["f32_convert_adjusted"] += 1
            by_kind[kind] += nbytes
            counts[kind] += 1
    return by_kind, counts


def run_cell(arch: str, shape_name: str, multi_pod: bool, strategy: str,
             save: bool = True, remat: str = "full"):
    shape = SHAPES[shape_name]
    cfg = get_config(arch)
    on, why = cell_enabled(cfg, shape)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    result = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
              "strategy": strategy, "enabled": on, "skip_reason": why}
    if not on:
        return result

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    specs = input_specs(arch, shape)
    jfn, args = jit_cell(mesh, specs, strategy=strategy,
                         flags=RunFlags(remat=remat))
    with jax.set_mesh(mesh):
        lowered = jfn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    ca = cost_analysis_dict(compiled)
    try:
        ma = compiled.memory_analysis()
        mem = {k: int(getattr(ma, k)) for k in
               ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes")
               if hasattr(ma, k)}
    except Exception as e:  # pragma: no cover
        mem = {"error": str(e)}

    hlo = compiled.as_text()
    coll_bytes, coll_counts = parse_collectives(hlo)

    result.update({
        "ok": True,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_hlo_once": float(ca.get("flops", 0.0)),
        "bytes_hlo_once": float(ca.get("bytes accessed", 0.0)),
        "memory": mem,
        "collective_bytes_once": coll_bytes,
        "collective_counts": coll_counts,
        "n_devices": int(np.prod(mesh.devices.shape)),
        "param_count": cfg.param_count(),
        "param_count_active": cfg.param_count(active_only=True),
    })
    if save:
        os.makedirs(RESULTS_DIR, exist_ok=True)
        fname = f"{arch}_{shape_name}_{mesh_name}_{strategy}.json"
        with open(os.path.join(RESULTS_DIR, fname), "w") as f:
            json.dump(result, f, indent=1)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--strategy", default="fsdp", choices=["fsdp", "2d"])
    ap.add_argument("--remat", default="full", choices=["full", "none"])
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        for arch, shape, on, _ in all_cells():
            cells.append((arch, shape.name))
    else:
        if not (args.arch and args.shape):
            ap.error("need --arch and --shape (or --all)")
        cells = [(args.arch, args.shape)]

    failures = 0
    for arch, shape_name in cells:
        try:
            r = run_cell(arch, shape_name, args.multi_pod, args.strategy,
                         remat=args.remat)
            if not r.get("enabled", True):
                print(f"SKIP {arch} {shape_name}: {r['skip_reason']}")
            else:
                print(f"OK   {arch} {shape_name} [{r['mesh']}] "
                      f"compile={r['compile_s']}s "
                      f"flops_once={r['flops_hlo_once']:.3g} "
                      f"coll={sum(r['collective_bytes_once'].values()):.3g}B")
        except Exception as e:
            failures += 1
            traceback.print_exc()
            print(f"FAIL {arch} {shape_name}: {type(e).__name__}: {e}")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
