"""Trace-time activation-sharding hook (batch DP + sequence parallelism).

Two jobs:

1. **Gather-safety** — XLA's SPMD partitioner (CPU pipeline) CHECK-fails
   when a gather's indices arrive pre-sharded over ``data`` beneath a
   manual ``pod`` sub-mesh.  The robust pattern: feed the batch sharded
   over ``pod`` only and constrain the *embedding output* onto ``data`` —
   GSPMD propagates batch sharding everywhere without partitioning the
   token gather's indices.

2. **Sequence parallelism** — between blocks, activations are additionally
   sharded over ``model`` on the sequence dim, so the ``lax.scan``-carried
   residuals (what remat saves per layer) occupy 1/TP of the memory.
   GSPMD inserts the all-gather before attention/matmuls and the
   reduce-scatter after — the standard SP schedule, visible in the
   dry-run's collective table.

3. **Per-shard kernels** — a Pallas kernel is one custom call that GSPMD
   cannot partition; :func:`map_batch_shards` runs it under ``shard_map``
   on each device's rows of the batch (and heads, over ``model``).

The step builders enter :func:`activation_sharding` around tracing; the
model calls :func:`shard_activations` at the embedding and at every block
boundary.  Outside any context the hook is a no-op, so single-device
tests are unaffected.
"""

from __future__ import annotations

import contextlib
import contextvars
import math
from typing import Callable, Optional, Tuple, Union

import jax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

Axes = Optional[Union[str, Tuple[str, ...]]]

_SPEC: contextvars.ContextVar = contextvars.ContextVar("repro_act_axes", default=None)


def _axis_size(name: Axes) -> int:
    """Size of a mesh axis in the ambient (context) mesh, 1 if unknown."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or name is None:
            return 1
        return int(mesh.shape.get(name, 1))
    except Exception:  # noqa: BLE001 — no ambient mesh
        return 1


@contextlib.contextmanager
def activation_sharding(batch_axes: Axes, seq_axes: Axes = None, mesh: Optional[Mesh] = None):
    """Declare mesh axes for the activation batch dim and (optionally) the
    sequence dim of [B, S, D] activations.  ``mesh`` is the step's mesh,
    for :func:`map_batch_shards` where no ``shard_map`` already names it."""
    token = _SPEC.set((batch_axes, seq_axes, mesh))
    try:
        yield
    finally:
        _SPEC.reset(token)


def shard_activations(x):
    """Constrain activations to the active (batch, seq) axes (no-op outside)."""
    spec = _SPEC.get()
    if spec is None:
        return x
    batch_axes, seq_axes, _ = spec
    if x.ndim >= 3 and seq_axes is not None and x.shape[1] > 1:
        return jax.lax.with_sharding_constraint(
            x, P(batch_axes, seq_axes, *([None] * (x.ndim - 2)))
        )
    return jax.lax.with_sharding_constraint(
        x, P(batch_axes, *([None] * (x.ndim - 1)))
    )


def shard_heads(x):
    """Constrain a [B, T, H, ...] tensor to (batch, None, tensor-axis, ...).

    Used by recurrences (WKV) whose chunked time axis must stay unsharded:
    re-laying the heads onto the model axis replaces a per-chunk
    all-gather of the full sequence with one cheap all-to-all.

    No-op when the head count doesn't divide the tensor axis — GSPMD would
    pad (e.g. yi-34b's 56 heads on a 16-way axis pad to 64) and the padded
    reshards measurably thrash (+11 s collective, §Perf yi iteration 1).
    """
    spec = _SPEC.get()
    if spec is None or x.ndim < 3:
        return x
    batch_axes, seq_axes, _ = spec
    if seq_axes is None:
        return x
    if x.shape[2] % max(_axis_size(seq_axes), 1) != 0:
        return x
    return jax.lax.with_sharding_constraint(
        x, P(batch_axes, None, seq_axes, *([None] * (x.ndim - 3)))
    )


def replicate_seq(x):
    """Constrain [B, S, ...] to batch-only sharding (seq gathered).

    Used for k/v ahead of the KV-block attention scan: gathering the
    (small) kv heads across the sequence beats all-gathering full-width
    activations by d_model / (2 * kv_heads * head_dim).
    """
    spec = _SPEC.get()
    if spec is None or x.ndim < 2:
        return x
    batch_axes, _, _ = spec
    return jax.lax.with_sharding_constraint(
        x, P(batch_axes, *([None] * (x.ndim - 1)))
    )


def map_batch_shards(fn: Callable, *xs):
    """``fn(*xs)`` on each device's shard of ``xs`` ([B, S, H, ...] each).

    For a Pallas kernel, which GSPMD cannot partition: under a mesh with an
    automatic axis wider than one device, ``fn`` runs inside ``shard_map``,
    manual over every such axis.  The batch is split over the active batch
    axes and the heads over the sequence-parallel axis, each where it
    divides evenly; every other axis holds whole copies.  ``fn`` must treat
    batch rows and heads independently and return one array laid out like
    ``xs[0]``.  Outside any context, or with no mesh to split over, it is
    ``fn(*xs)``.
    """
    spec = _SPEC.get()
    if spec is None:
        return fn(*xs)
    batch_axes, seq_axes, mesh = spec
    ambient = jax.sharding.get_abstract_mesh()
    mesh = mesh if ambient.empty else ambient
    if mesh is None:
        return fn(*xs)
    sizes = dict(mesh.shape)
    # a kernel lowers only where no axis is left to GSPMD, even of size one
    manual = jax.sharding.AxisType.Manual
    free = {n for n, t in zip(mesh.axis_names, mesh.axis_types) if t != manual}
    if all(sizes[n] == 1 for n in free):
        return fn(*xs)

    def split(axes, dim):
        axes = tuple(a for a in _names(axes) if a in free and sizes[a] > 1)
        if any(x.shape[dim] % math.prod(sizes[a] for a in axes) for x in xs):
            return None
        return axes or None

    dims = [None] * xs[0].ndim
    dims[0] = split(batch_axes, 0)
    dims[2] = split(seq_axes, 2)
    pspec = P(*dims)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=(pspec,) * len(xs), out_specs=pspec,
        axis_names=free, check_vma=False,
    )(*xs)


def _names(axes: Axes) -> Tuple[str, ...]:
    if axes is None:
        return ()
    return axes if isinstance(axes, tuple) else (axes,)
