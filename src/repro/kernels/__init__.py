"""Pallas TPU kernels for the perf-critical compute hot-spots.

Each kernel subpackage ships three modules:
  kernel.py -- pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    -- jit'd public wrapper (shape plumbing, interpret-mode switch)
  ref.py    -- pure-jnp oracle used by the tests' allclose sweeps

Off the TPU (the test suite runs with JAX_PLATFORMS=cpu) kernels are
validated with interpret=True (Python-level execution of the kernel body);
on a TPU the same pallas_call lowers to Mosaic -- tests/test_tpu_compile.py
compiles the codec kernels for a described v5e chip, and chip_smoke.py runs
them on one against their oracles.  The jnp model paths double as the
oracles.

Kernels:
  flash_attention  -- fused causal/bidir attention (training/prefill)
  decode_attention -- flash-decoding over a KV cache (serve_step)
  ssd_scan         -- Mamba2 SSD chunk kernel with carried state
  quant8           -- blockwise int8 quantize/dequant (gradient compression)
"""
