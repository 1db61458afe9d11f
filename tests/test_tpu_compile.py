"""The codec kernels compile for a TPU v5e chip (Mosaic, not interpret mode).

Ahead-of-time compiles for a described v5e device, without a chip: the
smollm-360m update size the codecs carry on the main path, and a ragged
1000-element vector.  The topology is described inside a fixture, never at
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
