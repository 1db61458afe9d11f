"""Mesh builders.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state.  The single-pod production mesh is 16x16 = 256
chips over ("data","model"); multi-pod prepends a "pod" axis (2x16x16 = 512
chips).  The dry-run launcher forces 512 host devices via XLA_FLAGS before
any jax import.

Every mesh has ``Auto`` axes: the models place activations with
``with_sharding_constraint`` hints (``distributed.sharding.hint``), which
JAX accepts only on Auto axes -- ``jax.make_mesh`` defaults to Explicit.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, devices=None):
    """Arbitrary mesh (tests use small ones, e.g. (2,4) on 8 host devices)."""
    shape = tuple(shape)
    return jax.make_mesh(shape, tuple(axes),
                         axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_data_mesh(devices=None):
    """("data","model") mesh with data = device count, model = 1: pure data
    parallelism over ``devices`` (default: every device of the host)."""
    devices = list(jax.devices() if devices is None else devices)
    return make_mesh((len(devices), 1), ("data", "model"), devices)
