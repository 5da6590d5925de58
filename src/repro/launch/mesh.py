"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so that
importing this module never touches jax device state — the dry-run must set
XLA_FLAGS before first jax init.

Axes:
  * ``data``  — batch / FSDP axis (16-way per pod)
  * ``model`` — tensor/expert-parallel axis (16-way, intra-pod ICI)
  * ``pod``   — multi-pod data-parallel axis (DCN); gradients all-reduce across it
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes, devices=None):
    """Mesh with ``Auto`` axes (``jax.make_mesh`` defaults to ``Explicit``,
    under which the sharding rules in ``repro.dist.sharding`` — written for
    GSPMD propagation — raise on gathers such as the embedding lookup).
    ``devices`` pins the mesh to an explicit device list, e.g. the chips of
    a gang reservation; None takes the first ``prod(shape)`` devices."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def data_axes(mesh) -> tuple:
    """Axes over which the batch is sharded (pod joins data when present)."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def fsdp_axis(mesh) -> str:
    return "data"


def model_axis(mesh) -> str:
    return "model"
