import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_FLAGS")
                           or "--xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

MUST be run as its own process (``python -m repro.launch.dryrun``): the first
lines pin the CPU platform and force 512 host placeholder devices BEFORE any
jax import -- jax locks the platform and device count on first init.  A
placeholder-device compile never needs an accelerator, so a dry-run started
next to a process that holds the chip never contends for it.  Tests override
the count via REPRO_XLA_FLAGS.

For each cell we record: memory_analysis (proves it fits), cost_analysis
(FLOPs/bytes for the roofline), and the collective-bytes breakdown parsed
from the partitioned HLO.  Results land in experiments/dryrun/*.json.
"""
import argparse       # noqa: E402
import json           # noqa: E402
import time           # noqa: E402
import traceback      # noqa: E402
from pathlib import Path  # noqa: E402

import jax            # noqa: E402

from repro.configs import SHAPES, get_arch, get_reduced, list_archs  # noqa: E402
from repro.distributed import roofline as rl                         # noqa: E402
from repro.distributed.step import build_step                        # noqa: E402
from repro.launch.mesh import make_mesh, make_production_mesh        # noqa: E402

OUT_DIR = Path(__file__).resolve().parents[3] / "experiments" / "dryrun"


def _mem_dict(mem) -> dict:
    keys = ["argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes"]
    out = {}
    for k in keys:
        v = getattr(mem, k, None)
        if v is not None:
            out[k] = int(v() if callable(v) else v)
    return out


def run_cell(arch_name: str, shape_name: str, mesh, mesh_desc: str,
             *, reduced: bool = False, save: bool = True) -> dict:
    arch = get_reduced(arch_name) if reduced else get_arch(arch_name)
    rec = {"arch": arch_name, "shape": shape_name, "mesh": mesh_desc,
           "reduced": reduced, "ok": False}
    t0 = time.time()
    try:
        if shape_name not in arch.shapes():
            rec["skipped"] = True
            rec["reason"] = ("encoder has no decode" if arch.model.is_encoder
                            else "full attention cannot run 500k context")
            rec["ok"] = True
            return rec
        step = build_step(arch, mesh, shape_name)
        with mesh:
            lowered = step.lower()
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            mem = compiled.memory_analysis()
            hlo = compiled.as_text()
        chips = mesh.devices.size
        total, active = rl.active_params(arch)
        mflops = rl.model_flops(arch, shape_name, total, active)
        rep = rl.analyze(compiled, hlo, arch_name=arch_name, shape=shape_name,
                         mesh_desc=mesh_desc, chips=chips, mflops=mflops,
                         extra={"t_lower_s": round(t_lower, 2),
                                "t_compile_s": round(t_compile, 2),
                                "params_total": total, "params_active": active})
        rec.update(rep.to_dict())
        rec["memory_analysis"] = _mem_dict(mem)
        rec["ok"] = True
    except Exception as e:  # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    finally:
        rec["t_total_s"] = round(time.time() - t0, 2)
    if save:
        OUT_DIR.mkdir(parents=True, exist_ok=True)
        tag = "reduced-" if reduced else ""
        p = OUT_DIR / f"{tag}{arch_name}__{shape_name}__{mesh_desc}.json"
        p.write_text(json.dumps(rec, indent=1, default=float))
    return rec


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--pods", default="both", choices=["1", "2", "both"])
    ap.add_argument("--mesh", default=None,
                    help="override mesh, e.g. '2x4' or '2x2x2' (test use)")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--no-save", action="store_true")
    args = ap.parse_args()

    archs = list_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = []
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        names = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
        meshes.append((make_mesh(dims, names), args.mesh))
    else:
        if args.pods in ("1", "both"):
            meshes.append((make_production_mesh(), "16x16"))
        if args.pods in ("2", "both"):
            meshes.append((make_production_mesh(multi_pod=True), "2x16x16"))

    n_fail = 0
    for mesh, desc in meshes:
        for a in archs:
            for s in shapes:
                rec = run_cell(a, s, mesh, desc, reduced=args.reduced,
                               save=not args.no_save)
                if rec.get("skipped"):
                    status = "SKIP " + rec["reason"]
                elif rec["ok"]:
                    status = (f"ok  comp={rec['t_compute_s']:.3e}s "
                              f"mem={rec['t_memory_s']:.3e}s "
                              f"coll={rec['t_collective_s']:.3e}s "
                              f"bound={rec['bottleneck']} "
                              f"frac={rec['roofline_fraction']:.3f}")
                else:
                    n_fail += 1
                    status = "FAIL " + rec.get("error", "?")
                print(f"[{desc}] {a} x {s}: {status}", flush=True)
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
