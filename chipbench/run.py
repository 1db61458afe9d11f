"""Run one cell of the benchmark on the accelerator this machine holds.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything the cell needs is found by its name in ``BENCHMARK.json``.  The
run refuses (exit 2, no result) where JAX finds no TPU or fewer chips than
the cell asks for, and where the program under test (``src/repro``) is not
beside the benchmark.  The last line of standard output is the result:
``{"correct", "attempted", "failed", "metrics", "device"[, "breakdown"],
"checks"}``; the numbers compared with the reference, each with its limit,
are also the last lines of standard error.
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


class Refused(Exception):
    pass


def devices_for(chips: int) -> list:
    """The first ``chips`` TPU devices; refuses anything else."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform {devices[0].platform!r}); "
                      "the benchmark runs only on the chip")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        if not (ROOT / "src" / "repro").is_dir():
            raise Refused(f"the program under test is not at {ROOT / 'src' / 'repro'}")
        sys.path[:0] = [str(ROOT), str(ROOT / "src")]
        from chipbench import bench
        from chipbench.peaks import peaks

        cell = bench.workload(args.workload)
        devices = devices_for(cell["chips"])
        kind = devices[0].device_kind
        peaks(kind)
    except (Refused, KeyError, FileNotFoundError) as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache

    import jax

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devices, T_START)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result))
    return 0


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices: list,
             t_start: float, *, fault: str | None = None, log=print,
             config: dict | None = None, mix: dict | None = None) -> dict:
    """Run a cell on ``devices`` past the look for a chip, and return the
    result object.  ``config`` and ``mix`` stand in for the cell's files (the
    tests run cells at reduced sizes); ``fault`` plants a fault in the timed
    path."""
    import jax

    from chipbench import bench, compare, generator
    from chipbench.cell import Context

    rec = bench.Record(workload=cell["name"],
                       config=config or bench.config(cell["config"]),
                       mix=mix or generator.load(cell["traffic"]),
                       device_kind=devices[0].device_kind, chips=len(devices))
    limits = bench.limits(cell["name"])
    log(f"cell {cell['name']}: config {cell['config']} traffic {cell['traffic']} "
        f"seed {seed} seconds {seconds} trace {int(trace)} mix {json.dumps(rec.mix)}")
    ctx = Context(rec=rec, seed=seed, seconds=seconds, trace=trace, devices=devices,
                  t_start=t_start, fault=fault, log=log)
    out = importlib.import_module(f"chipbench.{rec.kind}_cell").run(ctx)

    metrics = {}
    for m in bench.metrics_of(cell["name"], trace):
        v = bench.read_metric(m, rec)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    correct = out["finite"] and compare.judge(out["numbers"], limits)
    d = devices[0]
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"], "metrics": metrics,
              "device": {"platform": d.platform, "kind": d.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": out["memory_peak_bytes"]}}
    if trace and rec.trace is not None:
        result["device"]["busy_s"] = rec.trace.busy_s
        result["device"]["window_s"] = rec.trace.window_s
        result["breakdown"] = {"device_ops": rec.trace.top_ops,
                               "idle_gaps": rec.trace.idle_gaps}
    result["checks"] = compare.report(out["numbers"], limits)
    return result


if __name__ == "__main__":
    sys.exit(main())
