"""Readings that the cells' limits are set from, on the chip; the
benchmark's own runs never run this.

    python3 chipbench/readings.py --workload <name> --seeds 1,2,... \
        [--control-seeds a,b,c] [--fault <name> --fault-seeds x,y,z] \
        [--seconds 1] [--out <file.jsonl>]
    python3 chipbench/readings.py --workload <name> --set-limits <file.jsonl>

For each seed it runs the cell as a run does (set-up, a short window, the
comparison) and records the numbers compared.  The control is the plain
reference in the program's place, its matrix products in float8 (one
precision below the bfloat16 the configurations state): for training, its
first steps against the float32 reference's; for serving, at each position
of the program's own prompts and served tokens, the gap of the token the
control puts first.  A fault is planted in the program's timed path.  One
JSON object per reading goes to ``--out`` and to standard output.

``--set-limits`` sets the cell's limits (``limits/<workload>.json``) from
such readings, as ``limits()`` says.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent


def _seeds(text: str) -> list[int]:
    return [int(s) for s in text.split(",") if s]


#: a training fault sets a number's upper reading only where it reads this
#: many times the lower one; the control, and a state left unchanged, three
FAULT_FACTOR, CONTROL_FACTOR = 10.0, 3.0


def limits(readings: list[dict]) -> dict:
    """Each number's limit from the readings of one cell.  The lower reading
    is the largest of the sound runs; the upper one the smallest of the
    control, where that is three times the lower or more, and of each
    planted fault that reads ten times the lower or more (a state left
    unchanged reads 1 on the per-leaf numbers and needs no run).  The limit
    lies between them, twice as far in ratio from the lower as from the
    upper: lower^(1/3) * upper^(2/3).  A number with no upper reading gets
    no limit and is reported with ``"limit": null``."""
    by = {}
    for r in readings:
        for k, v in r["numbers"].items():
            by.setdefault(k, {}).setdefault(r["reading"], []).append(v)
    out = {}
    for k, groups in by.items():
        lower = max(groups["program"])
        cands = {}
        for name, vals in groups.items():
            if name == "program":
                continue
            factor = CONTROL_FACTOR if name == "control" else FAULT_FACTOR
            if min(vals) >= factor * lower:
                cands[name] = min(vals)
        if k in ("grad1_gap", "change_gap") and 1.0 >= CONTROL_FACTOR * lower:
            cands["fault:unchanged"] = 1.0
        upper = min(cands.values()) if cands else None
        out[k] = {"limit": None if upper is None else lower ** (1 / 3) * upper ** (2 / 3),
                  "lower": lower, "upper": upper,
                  "upper_from": min(cands, key=cands.get) if cands else None,
                  "readings": {n: [min(v), max(v), len(v)] for n, v in groups.items()}}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault", default=None)
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--set-limits", default=None, metavar="READINGS_JSONL")
    args = ap.parse_args(argv)
    if args.set_limits:
        rows = [json.loads(x) for x in Path(args.set_limits).read_text().splitlines() if x]
        lim = limits([r for r in rows if r["workload"] == args.workload])
        path = ROOT / "chipbench" / "limits" / f"{args.workload}.json"
        path.write_text(json.dumps(lim, indent=2) + "\n")
        print(json.dumps(lim))
        return 0
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from chipbench import bench, compare, generator
    from chipbench.cell import Context
    from chipbench.reference.common import FP8
    from chipbench.reference import serving as ref_serving, training as ref_training
    from chipbench.run import Refused, devices_for
    from repro.launch.compile_cache import enable_compile_cache

    cell = bench.workload(args.workload)
    try:
        devices = devices_for(cell["chips"])
    except Refused as e:
        print(f"readings: {e}", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = open(args.out, "a") if args.out else None

    def emit(obj):
        line = json.dumps(obj)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def run(seed, fault=None):
        rec = bench.Record(workload=cell["name"], config=bench.config(cell["config"]),
                           mix=generator.load(cell["traffic"]),
                           device_kind=devices[0].device_kind, chips=len(devices))
        ctx = Context(rec=rec, seed=seed, seconds=args.seconds, trace=False,
                      devices=devices, t_start=time.perf_counter(), fault=fault,
                      log=lambda *a: None)
        t = time.perf_counter()
        res = importlib.import_module(f"chipbench.{rec.kind}_cell").run(ctx)
        res["elapsed_s"] = time.perf_counter() - t
        res["setup_s"] = rec.setup_s
        return rec, res

    control_seeds = set(_seeds(args.control_seeds))
    for seed in _seeds(args.seeds):
        rec, res = run(seed)
        emit({"workload": cell["name"], "reading": "program", "seed": seed,
              "numbers": res["numbers"], "setup_s": res["setup_s"],
              "elapsed_s": res["elapsed_s"],
              "detail": {k: res[k] for k in ("prog", "ref") if k in res}})
        if seed not in control_seeds:
            continue
        t = time.perf_counter()
        if rec.kind == "train":
            vocab = rec.family.program_fields(rec.config)["vocab_size"]
            ctrl = ref_training.train_steps(
                rec.config, rec.family, seed,
                [generator.train_batch(rec.mix, vocab, seed, k)
                 for k in range(rec.mix["check_steps"])],
                rec.mix["optimizer"], num=FP8, block_rows=rec.mix["reference_rows"],
                devices=devices)
            numbers = compare.train_numbers(ctrl, res["ref"])
            detail = {"control": ctrl}
        else:
            gap = max(ref_serving.widest_gap(rec.config, rec.family, seed, seq, plen,
                                             dtype=rec.mix["dtype"], control=FP8,
                                             block_rows=rec.mix["reference_rows"])
                      for plen, seq in res["sample"])
            numbers, detail = {"served_logit_gap": gap}, {}
        emit({"workload": cell["name"], "reading": "control", "seed": seed,
              "numbers": numbers, "elapsed_s": time.perf_counter() - t,
              "detail": detail})
    for seed in _seeds(args.fault_seeds):
        _, res = run(seed, fault=args.fault)
        emit({"workload": cell["name"], "reading": f"fault:{args.fault}", "seed": seed,
              "numbers": res["numbers"], "elapsed_s": res["elapsed_s"],
              "detail": {k: res[k] for k in ("prog", "ref") if k in res}})
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
