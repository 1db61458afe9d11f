"""Time the splash attention kernel of causal train attention on a TPU, for
each block size and backward form, at smollm-360m's train shapes.

    PYTHONPATH=src python scripts/flash_block_sweep.py [--batch 8] [--seq 2048]

For each configuration it prints the median host wall ms, over 20 calls
each waited for, of one layer's forward and of its forward and backward (the
gradients of q, k and v), and their sum, which is what a ``remat="full"``
train step runs per layer (the forward, then the forward again and the
backward).  PERF.md §6 gives the sweep and why ``repro.models.attention``
uses the blocks it does.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import statistics
import sys
import time

import jax
import jax.numpy as jnp

from repro.models.attention import block_sizes, splash_kernel

BLOCKS = (256, 512, 1024)
KV_HEADS, GROUP, HEAD_DIM = 5, 3, 64      # smollm-360m: 15 query heads over 5


def median_ms(fn, *args, calls: int = 20) -> float:
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(calls):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - t0))
    return statistics.median(times)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=2048)
    a = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    b, s = a.batch, a.seq
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (b, KV_HEADS, GROUP, s, HEAD_DIM), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, KV_HEADS, s, HEAD_DIM), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, KV_HEADS, s, HEAD_DIM), jnp.bfloat16)
    do = jax.random.normal(ks[3], q.shape, jnp.bfloat16)
    print(f"device {jax.devices()[0].device_kind}; q {q.shape}, causal")
    print("block fused  fwd_ms  fwd_bwd_ms  layer_ms")
    for blk in BLOCKS:
        for fused in (False, True):
            kernel = jax.vmap(jax.vmap(splash_kernel(
                GROUP, s, block_sizes(blk, fused=fused), False)))
            fwd = jax.jit(kernel)
            fwd_bwd = jax.jit(lambda q, k, v, do: jax.vjp(kernel, q, k, v)[1](do))
            try:
                f, fb = median_ms(fwd, q, k, v), median_ms(fwd_bwd, q, k, v, do)
            except Exception as e:  # noqa: BLE001 -- a block the chip refuses is a result
                print(f"{blk:5d} {fused!s:5}  refused: {str(e).splitlines()[0][:120]}")
                continue
            print(f"{blk:5d} {fused!s:5}  {f:6.3f}  {fb:10.3f}  {f + fb:8.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
