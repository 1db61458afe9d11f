"""Kernel microbenchmarks.

CPU container: we time the pure-jnp oracle paths (the CPU execution baseline)
and report the model bytes each kernel must stream, i.e. the TPU roofline
floor time = bytes / 819 GB/s.  The Pallas kernels themselves are validated
in interpret mode (tests/test_kernels.py) -- interpret-mode timing is not
meaningful, so `derived` reports the v5e roofline floor instead.

This bench also closes the measured-MFU loop (DESIGN.md §16): it compiles
the full smollm-360m train_4k step on a 2x4 host mesh in a subprocess
(``repro.launch.dryrun`` -- jax pins the device count at first init) and
emits the compute-bound roofline fraction into the committed
``BENCH_kernels.json``, which ``PodPlatform(mfu="measured")`` and the
analytic planner's pod rows read (:mod:`repro.core.calibration`).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, emit_root, timeit
from repro.distributed.roofline import HBM_BW, PEAK_FLOPS
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.quant8.ops import int8_roundtrip
from repro.kernels.quant8.ref import quantize8_ref
from repro.kernels.topk_ef.ops import topk_ef
from repro.models.ssm import ssd_scan

#: the measured-MFU dry-run cell: full (non-reduced) arch so the useful-FLOPs
#: share reflects the real model, host mesh small enough to compile in ~5 s
MFU_ARCH, MFU_SHAPE, MFU_MESH = "smollm-360m", "train_4k", "2x4"
DRYRUN_DIR = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"


def measure_roofline_fraction() -> dict:
    """Run the MFU dry-run cell in a subprocess (CPU placeholder devices,
    see ``repro.launch.dryrun``) and return
    ``{"roofline_fraction": ..., "roofline_source": ...}``; raises if the
    compile fails."""
    from repro.core.calibration import compute_measured_mfu

    env = dict(os.environ,
               REPRO_XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", MFU_ARCH,
         "--shape", MFU_SHAPE, "--mesh", MFU_MESH],
        env=env, capture_output=True, text=True)
    artifact = DRYRUN_DIR / f"{MFU_ARCH}__{MFU_SHAPE}__{MFU_MESH}.json"
    if proc.returncode != 0 or not artifact.exists():
        raise RuntimeError(
            f"measured-MFU dryrun failed:\n{proc.stderr[-2000:]}")
    d = json.loads(artifact.read_text())
    if not d.get("ok") or d.get("skipped"):
        raise RuntimeError(f"measured-MFU dryrun cell not ok: {d.get('error')}")
    frac = compute_measured_mfu(d)
    return {
        "roofline_fraction": frac,
        "roofline_source": {
            "arch": MFU_ARCH, "shape": MFU_SHAPE, "mesh": MFU_MESH,
            "chips": d["chips"],
            "model_flops_global": d["model_flops_global"],
            "t_compute_s": d["t_compute_s"],
        },
    }


def run(quick: bool = True):
    rows = []
    rng = np.random.default_rng(0)

    # decode attention: b*m=16, S=32768, d=128, g=8
    bm, g, S, d = 16, 8, 32768 if not quick else 8192, 128
    qd = jnp.asarray(rng.standard_normal((bm, g, d)), jnp.bfloat16)
    kd = jnp.asarray(rng.standard_normal((bm, S, d)), jnp.bfloat16)
    fd = jax.jit(lambda q, k, v: decode_attention_ref(q, k, v, S,
                                                      sm_scale=d ** -0.5))
    t = timeit(lambda: jax.block_until_ready(fd(qd, kd, kd)))
    bytes_ = 2 * bm * S * d * 2
    rows.append({"name": "kern_decode_attention_ref", "us_per_call": t * 1e6,
                 "derived": f"cache_GB={bytes_ / 1e9:.3f};"
                            f"tpu_floor_us={bytes_ / HBM_BW * 1e6:.1f}"})

    # ssd scan: b=2, s=2048, h=16, p=64, n=64
    b, s2, h, p, n = 2, 2048, 16, 64, 64
    x = jnp.asarray(rng.standard_normal((b, s2, h, p)), jnp.float32)
    dt = jnp.abs(jnp.asarray(rng.standard_normal((b, s2, h)), jnp.float32))
    alog = jnp.asarray(rng.standard_normal(h) * 0.3, jnp.float32)
    B = jnp.asarray(rng.standard_normal((b, s2, n)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((b, s2, n)), jnp.float32)
    fs = jax.jit(lambda *a: ssd_scan(*a, 256)[0])
    t = timeit(lambda: jax.block_until_ready(fs(x, dt, alog, B, C)))
    ssd_flops = 2 * b * s2 * 256 * h * p + 4 * b * s2 * h * p * n
    rows.append({"name": "kern_ssd_scan_ref", "us_per_call": t * 1e6,
                 "derived": f"tpu_floor_us={ssd_flops / PEAK_FLOPS * 1e6:.2f}"})

    # quant8: 64 MB tensor
    nq = 16_000_000 if not quick else 4_000_000
    xq = jnp.asarray(rng.standard_normal((nq // 256, 256)), jnp.float32)
    fq = jax.jit(quantize8_ref)
    t = timeit(lambda: jax.block_until_ready(fq(xq)))
    bytes_q = nq * 5  # read fp32 + write int8
    rows.append({"name": "kern_quant8_ref", "us_per_call": t * 1e6,
                 "derived": f"cpu_GBps={bytes_q / t / 1e9:.1f};"
                            f"tpu_floor_us={bytes_q / HBM_BW * 1e6:.1f}"})

    # codec hot paths: the fused EF roundtrip and the topk filter exactly as
    # Int8EFCodec / TopKCodec execute them (ref backend = the CPU baseline
    # of the same padded-tile plumbing the Pallas kernels run on TPU)
    xc = jnp.asarray(rng.standard_normal((nq,)), jnp.float32)
    fr = lambda: jax.block_until_ready(int8_roundtrip(xc, backend="ref")[2])
    t = timeit(fr)
    # read fp32 + write int8 codes + fp32 scales + fp32 deq + fp32 err
    bytes_r = nq * (4 + 1 + 4 / 256 + 4 + 4)
    rows.append({"name": "kern_int8_roundtrip_ref", "us_per_call": t * 1e6,
                 "derived": f"cpu_GBps={bytes_r / t / 1e9:.1f};"
                            f"tpu_floor_us={bytes_r / HBM_BW * 1e6:.1f}"})

    kt = max(1, nq // 100)
    ft = lambda: jax.block_until_ready(topk_ef(xc, kt, backend="ref")[0])
    t = timeit(ft)
    bytes_t = nq * 12  # read fp32 + write kept + residual
    rows.append({"name": "kern_topk_ef_ref", "us_per_call": t * 1e6,
                 "derived": f"cpu_GBps={bytes_t / t / 1e9:.1f};"
                            f"tpu_floor_us={bytes_t / HBM_BW * 1e6:.1f}"})

    mfu = measure_roofline_fraction()
    emit_root("kernels", rows, quick=quick,
              peak_flops=PEAK_FLOPS, hbm_bw=HBM_BW, **mfu)
    return emit(rows, "bench_kernels")


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller tensors (CI smoke)")
    ap.add_argument("--full", action="store_true",
                    help="full-size tensors (overrides --quick)")
    args = ap.parse_args()
    run(quick=not args.full)
