import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
# ^ MUST precede any jax import: jax locks the device count on first init.
# Only the dry-run gets 512 placeholder devices; tests/benches see 1.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell and
extract the roofline terms from the compiled artifact.

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]

Per cell this records (experiments/dryrun/*.json):
  * compiled.memory_analysis()  — proves the cell fits 16 GB/chip;
  * compiled.cost_analysis()    — per-device HLO FLOPs / bytes accessed;
  * collective payload bytes parsed from the compiled HLO text;
  * the three roofline terms (TPU v5e: 197 TF bf16, 819 GB/s HBM,
    50 GB/s/link ICI) + dominant bottleneck + MODEL_FLOPS/HLO_FLOPs.
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax

from ..configs import LONG_OK, SHAPES, runnable_cells, skipped_cells
from ..runtime.hlo_cost import analyze as hlo_analyze

# hardware model (TPU v5e)
PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9
DCI_BW = 25e9  # cross-pod (not separately parsed; noted in EXPERIMENTS.md)
HBM_PER_CHIP = 16e9


def mesh_tag(multi_pod: bool) -> str:
    return "2x16x16" if multi_pod else "16x16"


def model_flops(cell, cfg) -> float:
    """6*N*D train / 2*N*D forward-only (global, per step)."""
    n_active = cfg.param_count(active_only=True)
    s, b = cell.shape.seq_len, cell.shape.global_batch
    if cell.shape.kind == "train":
        return 6.0 * n_active * s * b
    if cell.shape.kind == "prefill":
        return 2.0 * n_active * s * b
    return 2.0 * n_active * b  # decode: one token per sequence


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str | None):
    from .mesh import make_production_mesh
    from .specs import build_cell

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    cell = build_cell(arch, shape_name, mesh)

    t0 = time.time()
    donate = (0,) if cell.shape.kind == "train" else (
        (1,) if cell.shape.kind == "decode" else ()
    )
    jitted = jax.jit(cell.fn, out_shardings=cell.out_shardings,
                     donate_argnums=donate)
    # Ambient mesh: activates the model's sharding constraints.
    with jax.set_mesh(mesh):
        lowered = jitted.lower(*cell.in_specs)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    raw_cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    # trip-count-weighted analysis: compiled.cost_analysis() counts scan
    # bodies ONCE (verified), under-reporting layer stacks by 24-100x.
    cost = hlo_analyze(hlo)

    flops_dev = cost.flops
    bytes_dev = cost.bytes_accessed
    coll_bytes_dev = cost.total_collective_bytes

    t_compute = flops_dev / PEAK_FLOPS
    t_memory = bytes_dev / HBM_BW
    t_coll = coll_bytes_dev / ICI_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory, "collective_s": t_coll}
    dominant = max(terms, key=terms.get)

    mf = model_flops(cell, cell.cfg)
    mf_dev = mf / n_chips
    useful = mf_dev / flops_dev if flops_dev else 0.0

    mem_fields = {}
    for f in ("temp_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(mem, f, None)
        if v is not None:
            mem_fields[f] = int(v)
    peak_bytes = mem_fields.get("temp_size_in_bytes", 0) + max(
        mem_fields.get("argument_size_in_bytes", 0)
        + mem_fields.get("output_size_in_bytes", 0)
        - mem_fields.get("alias_size_in_bytes", 0),
        0,
    )

    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_tag(multi_pod),
        "chips": n_chips,
        "meta": cell.meta,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "per_device": {
            "hlo_flops": flops_dev,
            "hlo_dot_flops": cost.dot_flops,
            "hlo_bytes": bytes_dev,
            "collective_bytes": coll_bytes_dev,
            "collectives": cost.collective_bytes,
            "collective_counts": cost.collective_counts,
            "raw_cost_analysis_flops": float(raw_cost.get("flops", 0.0)),
            "raw_cost_analysis_bytes": float(raw_cost.get("bytes accessed", 0.0)),
        },
        "memory_analysis": mem_fields,
        "peak_bytes_per_device": peak_bytes,
        "fits_hbm": peak_bytes < HBM_PER_CHIP,
        "roofline": {
            **{k: float(v) for k, v in terms.items()},
            "dominant": dominant,
            "model_flops_global": mf,
            "model_flops_per_device": mf_dev,
            "useful_flops_ratio": useful,
        },
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        fname = f"{arch}__{shape_name}__{mesh_tag(multi_pod)}.json"
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(record, f, indent=1)
    return record


def fmt_row(r) -> str:
    t = r["roofline"]
    return (
        f"{r['arch']:<24} {r['shape']:<12} {r['mesh']:<8} "
        f"comp={t['compute_s']*1e3:8.2f}ms mem={t['memory_s']*1e3:8.2f}ms "
        f"coll={t['collective_s']*1e3:8.2f}ms dom={t['dominant']:<13} "
        f"peak={r['peak_bytes_per_device']/1e9:5.2f}GB "
        f"fit={'Y' if r['fits_hbm'] else 'N'} useful={t['useful_flops_ratio']:.2f} "
        f"compile={r['compile_s']:.0f}s"
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--continue-on-error", action="store_true")
    args = ap.parse_args()

    if args.all:
        cells = runnable_cells()
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        if args.shape == "long_500k" and args.arch.replace("-", "_").replace(".", "_") not in LONG_OK:
            print(f"SKIP {args.arch} long_500k (full attention)")
            return
        cells = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            try:
                rec = run_cell(arch, shape, mp, args.out)
                print(fmt_row(rec), flush=True)
            except Exception as e:
                failures.append((arch, shape, mp, repr(e)))
                print(f"FAIL {arch} {shape} {mesh_tag(mp)}: {e}", flush=True)
                if not args.continue_on_error:
                    traceback.print_exc()
                    sys.exit(1)
    for arch, shape, reason in skipped_cells():
        print(f"SKIP {arch:<24} {shape:<12} ({reason})")
    if failures:
        print(f"{len(failures)} FAILURES"); sys.exit(1)
    print("DRY-RUN OK")


if __name__ == "__main__":
    main()
