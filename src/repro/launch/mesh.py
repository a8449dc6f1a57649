"""Production meshes.

Factory functions (NOT module-level constants) so importing this module never
touches jax device state — the dry-run sets XLA_FLAGS before first jax init.

Target hardware: TPU v5e, 256 chips/pod (16×16 ICI torus), 2 pods via DCI.
  single-pod  (16, 16)        axes ("data", "model")
  multi-pod   (2, 16, 16)     axes ("pod", "data", "model")

The "data" axis hosts the decentralized gossip workers (paper's compute
nodes); "model" is intra-worker tensor parallelism; "pod" crosses the slow
DCI boundary — the BA-Topo heterogeneous machinery treats it exactly like
the paper's inter-server switch tier (core.constraints.pod_boundary_constraints).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "make_host_mesh", "POD_SHAPE",
           "MULTIPOD_SHAPE"]

POD_SHAPE = (16, 16)
MULTIPOD_SHAPE = (2, 16, 16)


def _check_devices(shape: tuple[int, ...], axes: tuple[str, ...]) -> None:
    """Fail early with an actionable message when the requested mesh does not
    fit the attached devices — XLA's own mesh-construction error on a
    CPU-only box is an opaque reshape failure with no hint about why."""
    need = math.prod(shape)
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"mesh {dict(zip(axes, shape))} needs {need} devices but only "
            f"{have} are attached ({jax.default_backend()} backend). On a "
            "CPU-only environment, simulate host devices by setting "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            "BEFORE the first jax import (e.g. in a subprocess, as "
            "tests/test_sharded_runtime.py does).")


def make_mesh(shape, axes):
    """The repo's one mesh constructor: every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which every op
    must agree on its operands' sharding types; the solver and the model
    code are written for GSPMD propagation (``Auto``), and inside
    ``shard_map`` the mapped axes become ``Manual`` either way."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = MULTIPOD_SHAPE if multi_pod else POD_SHAPE
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    _check_devices(shape, axes)
    return make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over whatever devices exist (CPU tests / examples)."""
    _check_devices((data, model), ("data", "model"))
    return make_mesh((data, model), ("data", "model"))
