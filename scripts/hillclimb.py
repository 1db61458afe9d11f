import os
os.environ["XLA_FLAGS"] = (os.environ.get("REPRO_XLA_FLAGS")
                           or "--xla_force_host_platform_device_count=512")
"""§Perf hillclimb driver: one (arch x shape x mesh) cell per invocation with
config overrides, printing the three roofline terms + collective/memory
breakdown.  Each hypothesis->change->measure iteration is one command:

  PYTHONPATH=src python scripts/hillclimb.py --arch llama3-405b \
      --shape train_4k --mesh 16x16 \
      --set train.remat=dots --set sharding.seq=None --tag L3
"""
import argparse     # noqa: E402
import dataclasses  # noqa: E402
import json         # noqa: E402
import time         # noqa: E402
from pathlib import Path  # noqa: E402


def parse_value(v: str):
    if v in ("None", "none", "null"):
        return None
    if v in ("True", "true"):
        return True
    if v in ("False", "false"):
        return False
    try:
        return int(v)
    except ValueError:
        pass
    try:
        return float(v)
    except ValueError:
        pass
    if "," in v:
        return tuple(parse_value(x) for x in v.split(","))
    return v


def apply_overrides(arch, sets):
    for kv in sets:
        key, val = kv.split("=", 1)
        section, field = key.split(".", 1)
        obj = getattr(arch, {"model": "model", "train": "train",
                             "sharding": "sharding"}[section])
        obj = dataclasses.replace(obj, **{field: parse_value(val)})
        arch = dataclasses.replace(arch, **{section: obj})
    return arch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--set", action="append", default=[], dest="sets")
    ap.add_argument("--tag", default="iter")
    ap.add_argument("--local-sgd", action="store_true",
                    help="measure MA-SGD/DiLoCo inner+outer instead of GA")
    ap.add_argument("--save", action="store_true")
    args = ap.parse_args()

    import jax  # noqa: F401
    from repro.configs import get_arch
    from repro.distributed import roofline as rl
    from repro.distributed.hlo_analysis import analyze_hlo
    from repro.launch.mesh import make_mesh

    dims = tuple(int(x) for x in args.mesh.split("x"))
    names = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    mesh = make_mesh(dims, names)
    arch = apply_overrides(get_arch(args.arch), args.sets)
    chips = mesh.devices.size
    total, active = rl.active_params(arch)
    mflops = rl.model_flops(arch, args.shape, total, active)

    t0 = time.time()
    if args.local_sgd:
        from repro.distributed.local_sgd import build_local_sgd
        ls = build_local_sgd(arch, mesh, args.shape)
        with mesh:
            ci = ls.lower_inner().compile()
            co = ls.lower_outer().compile()
        pod_sz = mesh.devices.size // mesh.shape["pod"]
        ri = analyze_hlo(ci.as_text(), pod_size=pod_sz)
        ro = analyze_hlo(co.as_text(), pod_size=pod_sz)
        H = arch.train.sync_period
        # effective per-step = inner + outer/H
        eff = {k: ri[k] + ro[k] / H for k in ("flops", "bytes", "coll_bytes")}
        rep = rl.RooflineReport(
            arch=args.arch, shape=args.shape, mesh=args.mesh, chips=chips,
            hlo_flops=eff["flops"], hlo_bytes=eff["bytes"],
            collective_bytes=eff["coll_bytes"], model_flops=mflops,
            collectives={"inner": ri["coll"], "outer": ro["coll"]})
        mem = ci.memory_analysis()
        extra = {"inner_coll_bytes": ri["coll_bytes"],
                 "outer_coll_bytes": ro["coll_bytes"], "H": H,
                 "inner_cross_pod_bytes": ri["cross_pod_bytes"],
                 "outer_cross_pod_bytes": ro["cross_pod_bytes"],
                 "cross_pod_bytes_per_step": ri["cross_pod_bytes"]
                 + ro["cross_pod_bytes"] / H}
    else:
        from repro.distributed.step import build_step
        step = build_step(arch, mesh, args.shape)
        with mesh:
            lowered = step.lower()
            compiled = lowered.compile()
        pod_sz = (mesh.devices.size // mesh.shape["pod"]
                  if "pod" in mesh.axis_names else None)
        rep = rl.analyze(compiled, compiled.as_text(), arch_name=args.arch,
                         shape=args.shape, mesh_desc=args.mesh, chips=chips,
                         mflops=mflops, pod_size=pod_sz)
        mem = compiled.memory_analysis()
        extra = {}

    d = rep.to_dict()
    d.update(extra)
    d["tag"] = args.tag
    d["sets"] = args.sets
    d["temp_bytes"] = int(getattr(mem, "temp_size_in_bytes", 0))
    d["t_build_s"] = round(time.time() - t0, 1)

    print(f"== {args.arch} x {args.shape} x {args.mesh} [{args.tag}] ==")
    print(f"  sets: {args.sets}")
    print(f"  t_compute    = {rep.t_compute:.3f} s")
    print(f"  t_memory     = {rep.t_memory:.3f} s")
    print(f"  t_collective = {rep.t_collective:.3f} s  (operand-bytes model)")
    print(f"  bottleneck   = {rep.bottleneck}   roofline_frac = "
          f"{rep.roofline_fraction:.4f}   useful/HLO flops = "
          f"{rep.flops_ratio:.3f}")
    adj = rep.extra.get("t_memory_kernel_adj_s")
    if adj is not None and rep.extra.get("scope_bytes", 0) > 0:
        bound_adj = max(rep.t_compute, adj, rep.t_collective)
        print(f"  [flash-kernel adj] t_memory = {adj:.3f} s -> "
              f"bound = {('compute' if bound_adj == rep.t_compute else 'memory' if bound_adj == adj else 'collective')} "
              f"frac = {rep.useful_time / bound_adj:.4f}")
    tadj = rep.extra.get("t_memory_tpu_adj_s")
    if tadj is not None:
        bound_t = max(rep.t_compute, tadj, rep.t_collective)
        print(f"  [+tpu-dtype adj]   t_memory = {tadj:.3f} s -> "
              f"frac = {rep.useful_time / bound_t:.4f}")
    print(f"  temp/device  = {d['temp_bytes'] / 2**30:.2f} GiB   "
          f"build = {d['t_build_s']}s")
    if not args.local_sgd:
        for k in ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute"):
            c = rep.collectives[k]
            if c["count"]:
                print(f"    {k:20s} {c['operand_bytes'] / 1e9:10.1f} GB  "
                      f"n={c['count']}")
    else:
        print(f"  inner coll = {extra['inner_coll_bytes'] / 1e9:.1f} GB  "
              f"outer coll = {extra['outer_coll_bytes'] / 1e9:.1f} GB  "
              f"H = {extra['H']}")
        print(f"  CROSS-POD bytes/step = inner {extra['inner_cross_pod_bytes'] / 1e9:.3f} GB"
              f" + outer/H {extra['outer_cross_pod_bytes'] / 1e9:.3f}/{extra['H']} GB"
              f" = {extra['cross_pod_bytes_per_step'] / 1e9:.3f} GB")
    if not args.local_sgd and rep.extra.get("cross_pod_bytes") is not None:
        print(f"  CROSS-POD bytes/step = "
              f"{rep.extra['cross_pod_bytes'] / 1e9:.3f} GB")
    if args.save:
        out = Path("experiments/perf")
        out.mkdir(parents=True, exist_ok=True)
        p = out / f"{args.arch}__{args.shape}__{args.mesh}__{args.tag}.json"
        p.write_text(json.dumps(d, indent=1, default=str))
        print(f"  saved {p}")


if __name__ == "__main__":
    main()
