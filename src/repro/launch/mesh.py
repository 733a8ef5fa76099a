"""Mesh construction for single-pod / multi-pod deployments.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  The production shapes are the assignment's: one pod =
16x16 = 256 chips (data x model), two pods = (2, 16, 16) with a leading
"pod" axis — batch shards over (pod, data), parameters' FSDP dim over the
same axes, tensor/expert parallelism over "model".

The same helpers serve local CPU runs (1-D data mesh over whatever devices
exist) so examples/tests run the identical code path at toy scale.
"""

from __future__ import annotations

import jax
import numpy as np


def _auto_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules here
    are GSPMD annotations.  (JAX's default is ``Explicit`` axes, under
    which the embedding gather and other sharded ops refuse to trace.)"""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_local_mesh(model_parallel: int = 1):
    """Dev mesh over the locally visible devices: (data, model)."""
    n = jax.device_count()
    if n % model_parallel:
        raise ValueError(f"{n} devices not divisible by mp={model_parallel}")
    return _auto_mesh((n // model_parallel, model_parallel),
                      ("data", "model"))


def make_elastic_mesh(n_chips: int, model_parallel: int):
    """Post-failure mesh over surviving chips (see runtime.faults.plan_
    elastic_mesh); used by the restart path."""
    from repro.runtime.faults import plan_elastic_mesh
    data, model = plan_elastic_mesh(n_chips, model_parallel)
    devs = np.asarray(jax.devices()[: data * model]).reshape(data, model)
    return jax.sharding.Mesh(devs, ("data", "model"))


def dp_axes(mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh (everything but 'model')."""
    return tuple(a for a in mesh.axis_names if a != "model")


def mesh_chips(mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))
