"""Mesh builders. Functions, not module constants — importing this module
never touches jax device state (required for the dry-run's
xla_force_host_platform_device_count to win the init race).

Every mesh here has ``Auto`` axes: the partition specs of
``repro.parallel.sharding`` are placement hints that XLA propagates
through the program (``jax.make_mesh`` would default to ``Explicit``
axes, under which e.g. the embedding gather on a model-sharded table is
a sharding-type error)."""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """The deployment mesh: one v5e pod 16x16 (data, model), or two pods
    2x16x16 (pod, data, model). 'pod' is the DCN axis.

    When more placeholder devices exist than the mesh needs (the dry-run
    allocates 512 host devices for both meshes), the first prod(shape) are
    used."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devs = jax.devices()
    if len(devs) >= n:
        return make_mesh_for_shape(shape, axes, devices=devs[:n])
    raise ValueError(
        f"need {n} devices for mesh {dict(zip(axes, shape))}, have "
        f"{len(devs)} — run under dryrun.py (it sets "
        f"xla_force_host_platform_device_count)")


def make_local_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU runs)."""
    return make_mesh_for_shape((data, model), ("data", "model"))


def make_mesh_for_shape(shape: tuple[int, ...], axes: tuple[str, ...],
                        devices=None):
    """Arbitrary topology (elastic-restart path uses this after a shrink),
    over ``devices`` (default: all of them)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)
