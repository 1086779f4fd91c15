"""Production meshes.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module does not touch jax device state.  The single-pod mesh
is 16x16 = 256 chips ("data", "model"); the multi-pod mesh adds a leading
"pod" axis: 2 x 16 x 16 = 512 chips.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.models.common import AxisRules, mesh_axis_sizes


def _auto(axes) -> tuple:
    """Auto axis types for every axis: the models and the fleet place
    their arrays with ``NamedSharding`` and ``with_sharding_constraint``
    and let the partitioner propagate the rest, which Explicit axes
    (``jax.make_mesh``'s default) refuse inside ``vmap`` and gathers."""
    return (AxisType.Auto,) * len(axes)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    need = 1
    for s in shape:
        need *= s
    devices = jax.devices()
    if len(devices) > need:   # e.g. single-pod mesh under a 512-device dry-run
        devices = devices[:need]
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=_auto(axes))


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (tests use small ones, elastic re-meshing uses this)."""
    return jax.make_mesh(shape, axes, axis_types=_auto(axes))


def make_fleet_mesh(*, wf: int | None = None, task: int = 1):
    """("wf", "task") mesh for the multi-workflow estimator fleet
    (``repro.online.fleet``): workflows shard over "wf", task rows over
    "task".  ``wf`` defaults to all remaining devices after the "task"
    axis takes ``task``; on a single device this is a (1, 1) mesh and
    ``shard_fleet`` replicates — the exact single-state layout.
    """
    n = len(jax.devices())
    if n % task != 0:
        raise ValueError(f"{n} devices not divisible by task={task}")
    if wf is None:
        wf = n // task
    return jax.make_mesh((wf, task), ("wf", "task"),
                         axis_types=_auto(("wf", "task")))


def make_rules(mesh, *, fsdp_over_pod: bool = False,
               overrides: dict | None = None) -> AxisRules:
    """Sharding rules for a mesh.

    Default multi-pod scheme is hierarchical: FSDP within a pod (ICI),
    pure data parallelism across pods (DCN) — gradients all-reduce over
    "pod", parameters are not gathered across pods every layer.
    ``fsdp_over_pod=True`` shards parameters/optimizer over the pod axis
    too (ZeRO across pods) — required for the 400B MoE to fit 16 GB chips.
    """
    names = mesh.axis_names
    sizes = mesh_axis_sizes(mesh)
    if "pod" in names:
        fsdp = ("pod", "data") if fsdp_over_pod else ("data",)
        return AxisRules(fsdp_axes=fsdp, dp_axes=("pod", "data"),
                         overrides=overrides or {}, axis_sizes=sizes)
    if "data" in names:
        return AxisRules(fsdp_axes=("data",), dp_axes=("data",),
                         overrides=overrides or {}, axis_sizes=sizes)
    return AxisRules(fsdp_axes=(), dp_axes=(), overrides=overrides or {},
                     axis_sizes=sizes)
