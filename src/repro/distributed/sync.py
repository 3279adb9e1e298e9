"""Cross-pod (inter-data-center) gradient synchronization strategies.

The mesh hierarchy mirrors the paper's deployment: ``data``/``model`` axes
live on intra-DC ICI; the ``pod`` axis is the WAN.  Inside the jitted step,
intra-pod reduction is GSPMD-automatic (reduce-scatter over ``data``
because parameters are FSDP-sharded), so whatever crosses the ``pod`` axis
here is exactly the WAN traffic the ScaleAcross fabric carries — each
strategy below corresponds to one row of the Fig. 14 / §Perf study:

* ``allreduce``  — flat psum over ``pod`` (the paper's M2 / DDP setting);
* ``ps``         — parameter-server emulation (paper's M1): gradients
                   gather to pod 0, the update happens there, parameters
                   broadcast back (2x full-volume WAN, server hot-spot);
* ``hier``       — hierarchical: identical bytes to ``allreduce`` per
                   device but chunked into ``num_channels`` independent
                   collectives = the QP/channel striping of §3.3 (each
                   chunk rides its own WAN flow; the fabric model assigns
                   ports via Algorithm 1);
* ``hier_int8``  — ``hier`` with int8+error-feedback compression on the
                   WAN hop only;
* ``local_sgd``  — no per-step WAN traffic; every H steps the runtime
                   triggers a DiLoCo-style outer step (see
                   ``repro.optim.diloco``).

All functions assume they run inside ``shard_map`` with the ``pod`` axis
manual (see ``repro.distributed.steps``).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from .compression import (
    apply_error_feedback,
    int8_compress,
    int8_decompress,
    residual,
)

STRATEGIES = ("allreduce", "ps", "hier", "hier_int8", "local_sgd")

def _chunk_bounds(dim0: int, num_channels: int):
    """Static slice bounds splitting dim 0 into <= num_channels parts."""
    base, rem = divmod(dim0, num_channels)
    bounds, start = [], 0
    for i in range(num_channels):
        size = base + (1 if i < rem else 0)
        if size == 0:
            break
        bounds.append((start, size))
        start += size
    return bounds


def _f32(grads):
    """Upcast before the WAN hop.

    Two reasons: (1) fp32 summation across pods is numerically safer than
    bf16 (and matches the paper's DDP fp32 gradient volumes); (2) XLA's
    SPMD partitioner CHECK-fails on bf16 all-reduces of 2-axis-sharded
    operands beneath a manual "pod" sub-mesh — the convert breaks the
    pattern (same family as the gather issue in act_sharding.py).
    """
    return jax.tree.map(lambda g: g.astype(jnp.float32), grads)


def sync_allreduce(grads, *, axis: str = "pod"):
    """Flat cross-pod mean (paper M2)."""
    with jax.named_scope("sync"):
        n = jax.lax.psum(1, axis)
        return jax.tree.map(lambda g: jax.lax.psum(g, axis) / n, _f32(grads))


def sync_hier(grads, *, axis: str = "pod", num_channels: int = 4):
    """Channel-striped cross-pod mean: large leaves split along their
    leading (layer-stack) dim into ``num_channels`` independent psums —
    the JAX-native analogue of NCCL multi-QP striping (§3.3): distinct
    flows on the WAN that the queue-pair-aware allocator spreads over
    distinct ECMP paths.  The leading stack dim is replicated in our
    sharding rules, so the split never forces a GSPMD reshard (a flat
    ``reshape(-1)`` would all-gather every leaf — measured +14 GiB/device
    on phi-3-vision).
    """
    with jax.named_scope("sync"):
        n = jax.lax.psum(1, axis)

        def one(g):
            if g.ndim == 0 or g.shape[0] < 2:
                return jax.lax.psum(g, axis) / n
            parts = [
                jax.lax.psum(jax.lax.slice_in_dim(g, s, s + size, axis=0), axis)
                for s, size in _chunk_bounds(g.shape[0], num_channels)
            ]
            return jnp.concatenate(parts, axis=0) / n

        return jax.tree.map(one, _f32(grads))


def sync_hier_int8(grads, ef, *, axis: str = "pod"):
    """int8 + error feedback on the WAN hop.

    Pattern: g' = g + ef; q = quant(g'); all-gather(q) over pod; dequant &
    mean locally; new ef = g' - dequant(q_local).  Only int8 payloads (+
    fp32 block scales, ~1.6%) cross the WAN.
    Returns (synced grads, new error feedback).
    """
    with jax.named_scope("sync"):
        n = jax.lax.psum(1, axis)
        boosted = apply_error_feedback(grads, ef)

        def one(g):
            c = int8_compress(g)
            vals = jax.lax.all_gather(c.values, axis)  # (npods, ..., L) int8
            scls = jax.lax.all_gather(c.scales, axis)  # (npods, ..., L/B) f32
            nblocks = c.scales.shape[-1]
            blocks = vals.reshape(*vals.shape[:-1], nblocks, -1).astype(jnp.float32)
            deq = (blocks * scls[..., None]).reshape(vals.shape).sum(0)
            mean = deq[..., : c.orig_last].reshape(c.orig_shape) / n
            local_deq = int8_decompress(c)
            return mean, local_deq

        flat, treedef = jax.tree.flatten(boosted)
        synced, transmitted = [], []
        for g in flat:
            m, t = one(g)
            synced.append(m)
            transmitted.append(t)
        synced = jax.tree.unflatten(treedef, synced)
        transmitted = jax.tree.unflatten(treedef, transmitted)
        new_ef = residual(boosted, transmitted)
        return synced, new_ef


def sync_ps(grads, params, apply_update: Callable, *, axis: str = "pod"):
    """Parameter-server emulation (paper M1).

    Workers push gradients to the server (pod 0), the server applies the
    update, workers pull fresh parameters.  Expressed with collectives:
    all-gather(grads) [push], masked update on pod 0, psum-broadcast of the
    updated params [pull].  WAN volume = grads + params per step, matching
    the paper's observation that PS moves ~1.5x the bytes of AllReduce
    (459 MB vs 312 MB per batch) and concentrates them on one site.

    ``apply_update(grads) -> new_params-like pytree`` runs only on pod 0's
    values (identical computation everywhere; non-0 pods discard).
    Returns the broadcast updated params.
    """
    with jax.named_scope("sync"):
        # push: server receives every pod's gradients
        gathered = jax.tree.map(lambda g: jax.lax.all_gather(g, axis), grads)
        g_mean = jax.tree.map(lambda g: g.mean(0), gathered)
        updated = apply_update(g_mean)
        # pull: only the server's copy survives the broadcast
        is_server = (jax.lax.axis_index(axis) == 0).astype(jnp.float32)

        def bcast(u):
            return jax.lax.psum(u * is_server.astype(u.dtype), axis)

        return jax.tree.map(bcast, updated)


def sync_local(grads):
    """local_sgd: no WAN traffic in the inner step."""
    return grads


def wan_bytes_per_step(params_size_bytes: int, strategy: str, *, npods: int = 2) -> float:
    """Analytic WAN byte volume per pod per step (for the §Perf table)."""
    if strategy == "allreduce":
        return 2 * (npods - 1) / npods * params_size_bytes
    if strategy == "ps":
        return 2.0 * params_size_bytes  # push grads + pull params
    if strategy == "hier":
        return 2 * (npods - 1) / npods * params_size_bytes
    if strategy == "hier_int8":
        return (npods - 1) * (params_size_bytes / 4 * 1.016)  # int8 + scales
    if strategy == "local_sgd":
        return 0.0
    raise ValueError(strategy)
