"""Production mesh construction.

``pod`` is the data-center axis: collectives crossing it ride the WAN/DCI
modeled by :mod:`repro.core` — exactly the traffic class the paper's fabric
carries.  ``data`` is intra-pod data parallelism (+ FSDP sharding), and
``model`` is tensor/expert parallelism.

Defined as FUNCTIONS so importing this module never touches jax device
state (device count locks on first backend initialization).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def make_mesh(
    shape: Tuple[int, ...], axes: Tuple[str, ...], *, devices: Optional[Sequence] = None
) -> Mesh:
    """Mesh with every axis GSPMD-auto (the train step's ``shard_map`` makes
    ``pod`` manual) over ``devices`` (default: all of the process's devices).
    Used by the launchers, tests and the elastic re-mesh."""
    return jax.make_mesh(
        shape, axes, axis_types=(AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(
    *, pods: int = 1, data: Optional[int] = None, model: int = 1
) -> Mesh:
    """Best-effort mesh over however many (possibly fake) devices exist.

    Used by smoke/integration tests that run under
    ``--xla_force_host_platform_device_count=N``.
    """
    n = len(jax.devices())
    if data is None:
        data = n // (pods * model)
    assert pods * data * model == n, (pods, data, model, n)
    if pods > 1:
        return make_mesh((pods, data, model), ("pod", "data", "model"))
    return make_mesh((data, model), ("data", "model"))


def batch_axes(mesh: Mesh) -> Tuple[str, ...]:
    """Mesh axes the global batch is sharded over."""
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def num_pods(mesh: Mesh) -> int:
    return mesh.shape.get("pod", 1)


def chips_per_pod(mesh: Mesh) -> int:
    total = 1
    for size in mesh.shape.values():
        total *= size
    return total // num_pods(mesh)
