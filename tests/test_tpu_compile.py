"""The codec kernels, the serving prefill and the train steps compile for
TPU v5e chips.

Ahead-of-time compiles for described v5e devices, without a chip: the
codec kernels (Mosaic, not interpret mode) at the smollm-360m update size
the codecs carry on the main path and at a ragged 1000-element vector,
smollm-360m's chunked prefill program at the serve cell's shapes, and the
train steps at the train cells' shapes, where smollm-360m's attention runs
as the splash kernel, also inside local SGD's per-pod ``shard_map``.  The topology is described inside a fixture, never at
import: only one process at a time may load the TPU library, and the suite
runs under several workers.  The persistent compilation cache is off around
these compiles, since an entry compiled for a described chip cannot be
read back without one.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_arch
from repro.configs.base import ShapeConfig
from repro.distributed.local_sgd import build_local_sgd
from repro.distributed.step import build_train_step
from repro.kernels.quant8.ops import _int8_roundtrip
from repro.kernels.topk_ef.ops import _topk_ef
from repro.launch.mesh import make_data_mesh
from repro.models import attention, build_model


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


SIZES = {"smollm-360m": None, "ragged-1000": 1000}


def _size(name):
    return SIZES[name] or build_model(get_arch(name)).param_count()


@pytest.mark.parametrize("size", list(SIZES))
@pytest.mark.parametrize("kernel", ["int8_roundtrip", "topk_ef"])
def test_codec_kernel_compiles_for_v5e(one_chip, kernel, size):
    n = _size(size)
    x = jax.ShapeDtypeStruct((n,), jnp.float32, sharding=one_chip)
    if kernel == "int8_roundtrip":
        lowered = _int8_roundtrip.lower(x, interpret=False, backend="kernel")
    else:
        lowered = _topk_ef.lower(x, k=max(1, n // 100), interpret=False,
                                 backend="kernel")
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_prefill_chunk_fits_one_v5e(one_chip):
    """smollm-360m's chunk program at b16, C256, cache 768, bf16: it
    compiles for one chip under its own name, and its arguments plus
    temporaries fit the chip's 16 GB."""
    model = build_model(get_arch("smollm-360m"))
    assert model.cfg.dtype == "bfloat16"

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(on_chip, model.abstract())
    cache = jax.tree.map(on_chip, model.init_cache(16, 768, abstract=True))
    tokens = on_chip(jax.ShapeDtypeStruct((16, 256), jnp.int32))
    scalar = on_chip(jax.ShapeDtypeStruct((), jnp.int32))
    compiled = jax.jit(model.prefill_chunk).lower(
        params, cache, tokens, scalar, scalar).compile()
    assert compiled.as_text().startswith("HloModule jit_prefill_chunk")
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


KERNELS = re.compile(r"%splash_mqa_(fwd|dq|dkv)_[\w.]+ = .* custom-call\(")
STEPS = {  # (arch, chips): the splash kernels its step must hold
    ("smollm-360m", 1): {"fwd", "dq", "dkv"},
    ("mamba2-370m", 1): set(),
    ("smollm-360m", 4): {"fwd", "dq", "dkv"},
}


@pytest.mark.parametrize("name,chips", list(STEPS))
def test_train_step_attention_kernel_for_v5e(topo, monkeypatch, name, chips):
    """The train cells' step (8 rows of 2048 a chip, ``remat="full"``,
    all-reduced gradients) on a chips x 1 data mesh of described v5e chips,
    with ``attention`` seeing a TPU backend.  smollm-360m's holds the splash
    kernel's forward, dq and dkv calls and no fp32 2048 x 2048 score
    buffer; mamba2-370m's holds no kernel; and no step all-gathers: on four
    chips the kernel runs on each chip's own rows (``shard_map``)."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    arch = get_arch(name)
    arch = arch.replace(train=dataclasses.replace(
        arch.train, remat="full", comm_pattern="allreduce", micro_batches=1))
    b, s = 8 * chips, 2048
    mesh = make_data_mesh(topo.devices[:chips])
    specs = {k: jax.ShapeDtypeStruct((b, s), jnp.int32) for k in ("tokens", "labels")}
    step = build_train_step(arch, mesh, ShapeConfig("v5e", s, b, "train"),
                            batch_specs=specs)
    args = jax.tree.map(
        lambda x, sh: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh),
        step.in_specs, step.in_shardings)
    with mesh:
        hlo = step.fn.lower(*args).compile().as_text()
    assert set(KERNELS.findall(hlo)) == STEPS[name, chips]
    assert not re.search(r"all-gather(-start)?\(", hlo)
    if name == "smollm-360m":
        assert not re.search(r"f32\[(\d+,)*2048,2048\]", hlo)


def test_local_sgd_step_attention_kernel_for_v5e(topo, monkeypatch):
    """smollm-360m's local-SGD inner step (``--algorithm ma_sgd``) on a
    2 x 2 x 1 ("pod", "data", "model") mesh of described v5e chips, 8 rows
    of 2048 a pod: inside the ``shard_map`` that holds "pod" manual, the
    kernel runs over "data" alone, and no attention operand is all-gathered
    (the step's only all-gathers are the embedding gradient's)."""
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    arch = get_arch("smollm-360m")
    arch = arch.replace(train=dataclasses.replace(
        arch.train, remat="full", comm_pattern="allreduce", micro_batches=1,
        algorithm="ma_sgd"))
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2, 1),
                ("pod", "data", "model"))
    ls = build_local_sgd(arch, mesh, ShapeConfig("v5e", 2048, 16, "train"))
    with mesh:
        hlo = ls.lower_inner().compile().as_text()
    assert set(KERNELS.findall(hlo)) == {"fwd", "dq", "dkv"}
    gathers = [ln for ln in hlo.splitlines() if re.search(r"all-gather(-start)?\(", ln)]
    assert not [ln for ln in gathers if "attn" in ln]
    assert not re.search(r"f32\[(\d+,)*2048,2048\]", hlo)
