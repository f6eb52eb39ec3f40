"""Per-shape collective breakdown of one dry-run cell (hillclimb tooling).

    PYTHONPATH=src python -m repro.launch.collective_report --arch X --shape Y
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")

import argparse

import jax

from ..runtime import hlo_cost as H
from ..runtime.hlo_analysis import shape_bytes


def report(arch: str, shape: str, multi_pod: bool = False, top: int = 15):
    from .mesh import make_production_mesh
    from .specs import build_cell

    mesh = make_production_mesh(multi_pod=multi_pod)
    cell = build_cell(arch, shape, mesh)
    donate = (0,) if cell.shape.kind == "train" else (
        (1,) if cell.shape.kind == "decode" else ())
    jitted = jax.jit(cell.fn, out_shardings=cell.out_shardings,
                     donate_argnums=donate)
    with jax.set_mesh(mesh):
        txt = jitted.lower(*cell.in_specs).compile().as_text()
    comps = H._parse(txt)

    fusion_internal, referenced = set(), set()
    for c in comps.values():
        for i in c.instrs:
            for m in H._CALLS.finditer(i.args):
                fusion_internal.add(m.group(1))
            for m in H._TO_APPLY.finditer(i.args):
                fusion_internal.add(m.group(1))
    referenced |= fusion_internal
    for c in comps.values():
        for i in c.instrs:
            for pat in (H._BODY, H._COND):
                m = pat.search(i.args)
                if m:
                    referenced.add(m.group(1))
    entries = [n for n in comps if n not in referenced]
    weights: dict[str, float] = {}

    def visit(name, w):
        c = comps.get(name)
        if c is None:
            return
        weights[name] = weights.get(name, 0) + w
        for i in c.instrs:
            if i.op == "while":
                t = 1
                tm = H._TRIP.search(i.args)
                if tm:
                    t = int(tm.group(1))
                bm, cm = H._BODY.search(i.args), H._COND.search(i.args)
                if bm:
                    visit(bm.group(1), w * t)
                if cm:
                    visit(cm.group(1), w * (t + 1))
            else:
                for m in H._CALLS.finditer(i.args):
                    visit(m.group(1), w)

    for e in entries:
        visit(e, 1.0)

    rows = []
    for name, c in comps.items():
        w = weights.get(name, 0)
        if not w:
            continue
        for i in c.instrs:
            base = i.op.removesuffix("-start")
            if base in H.COLLECTIVE_OPS:
                rows.append((shape_bytes(i.type_str) * w, base,
                             i.type_str[:60], w, name[:40]))
    rows.sort(reverse=True)
    total = sum(r[0] for r in rows)
    print(f"total collective bytes/device/step: {total/1e9:.2f} GB "
          f"({len(rows)} sites)")
    for r in rows[:top]:
        print(f"{r[0]/1e9:7.2f}GB {r[1]:<19} w={r[3]:<7.0f} {r[2]}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--top", type=int, default=15)
    a = ap.parse_args()
    report(a.arch, a.shape, a.multi_pod, a.top)
