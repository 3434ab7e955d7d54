"""Mesh construction: every mesh in the repo is built here.

Single pod: 16 x 16 = 256 chips, axes ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips, axes ("pod", "data", "model") — the
"pod" axis carries only data parallelism (gradient all-reduce), i.e. the
only collectives crossing the inter-pod DCN are reductions, optionally
int8-compressed (repro.train.fault.compressed_gradient).

All axes are ``AxisType.Auto``: the model code places data through
sharding *constraints* (``parallel.sharding.constrain``) and manual
``shard_map`` regions, both of which need Auto axes. ``jax.make_mesh``
defaults to ``Explicit`` axes, under which those constraints are
refused, so no caller builds a mesh with it directly.

Functions, not module constants: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first init).
"""
from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> jax.sharding.Mesh:
    """A device mesh with Auto axes (see module docstring).

    >>> make_mesh((1,), ("data",)).axis_types
    (Auto,)
    """
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model_parallel: int = 1):
    """Whatever-is-available mesh for tests / elastic re-meshing demos."""
    n = len(jax.devices())
    from repro.train.fault import remesh_plan

    data, model = remesh_plan(n, model_parallel)
    return make_mesh((data, model), ("data", "model"))


def force_host_device_count(n: int) -> None:
    """Forge ``n`` virtual CPU devices via ``XLA_FLAGS``.

    Must run before the XLA backend initializes — importing jax is fine,
    touching devices/arrays is not (the flag is read once at backend
    init). A count already present in ``XLA_FLAGS`` wins, so an explicit
    environment (CI jobs, tests/conftest.py) is never overridden.
    """
    import os

    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        )
