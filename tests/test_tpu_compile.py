"""The codec kernels and the serving prefill compile for a TPU v5e chip.

Ahead-of-time compiles for a described v5e device, without a chip: the
codec kernels (Mosaic, not interpret mode) at the smollm-360m update size
the codecs carry on the main path and at a ragged 1000-element vector, and
smollm-360m's chunked prefill program at the serve cell's shapes.  The topology is described inside a fixture, never at
import: only one process at a time may load the TPU library, and the suite
runs under several workers.  The persistent compilation cache is off around
these compiles, since an entry compiled for a described chip cannot be
read back without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.quant8.ops import _int8_roundtrip
from repro.kernels.topk_ef.ops import _topk_ef
from repro.models import build_model


@pytest.fixture(scope="module")
def one_chip():
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
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


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
