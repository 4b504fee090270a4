"""Mesh construction (TPU v5e hosts and pods).

Single pod: (data=16, model=16) = 256 chips. Multi-pod: (pod=2, data=16,
model=16) = 512 chips — the pod axis joins data parallelism by default (or
pipeline stages when pipeline parallelism is enabled).

Every mesh is built through ``build_mesh`` with ``Auto`` axes: the model
states its layout as ``with_sharding_constraint`` intents
(``collectives.constrain``), which are hints under ``Auto`` axes and hard
asserts under the ``Explicit`` axes ``jax.make_mesh`` defaults to.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run forces a 512-device host platform before first init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def build_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return build_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """(data, model) mesh over every local device."""
    n = len(jax.devices())
    assert n % model == 0
    return build_mesh((n // model, model), ("data", "model"))


def data_parallel_size(mesh) -> int:
    """Devices along the data-parallel axes (everything but "model")."""
    n = 1
    for size, name in zip(mesh.devices.shape, mesh.axis_names):
        if name != "model":
            n *= size
    return n


def choose_microbatches(cfg, dp: int, global_batch: int) -> int:
    """Enough gradient accumulation that activations fit 16 GB/chip."""
    n = cfg.param_count()
    if n > 1e11:
        micro = 16
    elif n > 1.5e10:
        micro = 8
    elif n > 4e9:
        micro = 4
    else:
        micro = 1
    local = max(global_batch // dp, 1)
    return max(1, min(micro, local))
