import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape)
on the production meshes and capture memory/cost/collective analysis.

MUST be run as its own process (the device-count flag above is read at
first jax init, BEFORE any other import - hence the file's first two
lines).  Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch all --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --arch glm4-9b \
        --shape train_4k --mesh single

Results land as JSON under results/dryrun/ (one file per cell x mesh);
EXPERIMENTS.md §Dry-run and the roofline benchmark read them.
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs import ARCH_IDS, get_arch
from repro.launch import hlo_analysis as H
from repro.launch.mesh import (device_bytes_estimate, make_production_mesh,
                               tree_named_shardings)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _measure(cell, mesh) -> dict:
    """Lower + compile one cell on one mesh; return all analyses."""
    t0 = time.time()
    with jax.set_mesh(mesh):
        in_sh = tree_named_shardings(cell.in_shardings, mesh)
        out_sh = (tree_named_shardings(cell.out_shardings, mesh)
                  if cell.out_shardings is not None else None)
        jitted = jax.jit(cell.fn, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=cell.donate or ())
        lowered = jitted.lower(*cell.arg_specs)
        lower_s = time.time() - t0
        t1 = time.time()
        compiled = lowered.compile()
        compile_s = time.time() - t1
    txt = compiled.as_text()
    return {
        "lower_s": lower_s, "compile_s": compile_s,
        "memory_analysis": H.memory_dict(compiled),
        "cost_analysis": H.cost_dict(compiled),
        "collectives": H.collective_bytes(txt),
        "hlo_chars": len(txt),
    }


def _corrected(main: dict, m_p: dict, m_2p: dict, trips: int,
               period: int) -> dict:
    """XLA counts while-loop bodies once; extrapolate from the p vs 2p
    layer-count variants: corrected = m(p) + (trips/p - 1) * (m(2p)-m(p))."""
    n_periods = trips // period
    out = {}
    for key in ("flops", "bytes_accessed", "transcendentals"):
        a = m_p["cost_analysis"].get(key, 0.0)
        b = m_2p["cost_analysis"].get(key, 0.0)
        out[key] = a + (n_periods - 1) * max(0.0, b - a)
    a = m_p["collectives"].get("total", 0)
    b = m_2p["collectives"].get("total", 0)
    out["collective_total"] = a + (n_periods - 1) * max(0, b - a)
    out["per_period_flops"] = max(
        0.0, m_2p["cost_analysis"].get("flops", 0.0)
        - m_p["cost_analysis"].get("flops", 0.0))
    return out


def run_cell(cell, *, multi_pod: bool, verbose: bool = True) -> dict:
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {
        "arch": cell.arch_id, "shape": cell.shape_name, "kind": cell.kind,
        "mesh": "x".join(map(str, mesh.devices.shape)),
        "n_chips": mesh.size, "ok": False, "meta": cell.meta,
    }
    try:
        rec.update(_measure(cell, mesh))
        rec["arg_bytes_per_device"] = device_bytes_estimate(
            cell.arg_specs, cell.in_shardings, mesh)
        if cell.variant_fn is not None and cell.loop_trips:
            p = cell.loop_period
            m_p = _measure(cell.variant_fn(p), mesh)
            m_2p = _measure(cell.variant_fn(2 * p), mesh)
            rec["corrected"] = _corrected(rec, m_p, m_2p,
                                          cell.loop_trips, p)
        rec["ok"] = True
        if verbose:
            ca, co = rec["cost_analysis"], rec["collectives"]
            flops = rec.get("corrected", {}).get("flops",
                                                 ca.get("flops", 0))
            coll = rec.get("corrected", {}).get("collective_total",
                                                co.get("total", 0))
            print(f"[dryrun] {cell.arch_id}/{cell.shape_name} "
                  f"mesh={rec['mesh']} OK "
                  f"lower={rec['lower_s']:.1f}s compile={rec['compile_s']:.1f}s "
                  f"flops/dev={flops:.3e} coll/dev={coll:.3e}B")
            if rec["memory_analysis"]:
                print(f"         memory_analysis: {rec['memory_analysis']}")
    except Exception as e:  # noqa: BLE001 - a failed cell is a data point
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"[dryrun] {cell.arch_id}/{cell.shape_name} "
                  f"mesh={rec['mesh']} FAILED: {rec['error']}")
    return rec


def save_record(rec: dict, out_dir: str):
    os.makedirs(out_dir, exist_ok=True)
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id or 'all' (see configs.ARCH_IDS)")
    ap.add_argument("--shape", default=None, help="one shape name only")
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--out", default=os.path.abspath(RESULTS_DIR))
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    archs = list(ARCH_IDS) if args.arch == "all" else [args.arch]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_ok = n_fail = n_skip = 0
    for arch_id in archs:
        mod = get_arch(arch_id)
        shapes = [args.shape] if args.shape else list(mod.SHAPES)
        for shape in shapes:
            if shape in getattr(mod, "SKIPPED_SHAPES", {}):
                print(f"[dryrun] {arch_id}/{shape} SKIPPED: "
                      f"{mod.SKIPPED_SHAPES[shape]}")
                for multi in meshes:
                    rec = {"arch": arch_id, "shape": shape,
                           "mesh": "2x16x16" if multi else "16x16",
                           "ok": True, "skipped": True,
                           "reason": mod.SKIPPED_SHAPES[shape]}
                    save_record(rec, args.out)
                n_skip += 1
                continue
            for multi in meshes:
                mesh_name = "2x16x16" if multi else "16x16"
                path = os.path.join(
                    args.out, f"{arch_id}__{shape}__{mesh_name}.json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        if json.load(f).get("ok"):
                            n_skip += 1
                            continue
                cell = mod.make_cell(shape)
                rec = run_cell(cell, multi_pod=multi)
                save_record(rec, args.out)
                n_ok += rec["ok"]
                n_fail += (not rec["ok"])
    print(f"[dryrun] done: ok={n_ok} fail={n_fail} skipped={n_skip}")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
