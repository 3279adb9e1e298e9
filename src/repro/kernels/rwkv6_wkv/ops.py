"""Jit-ready wrapper for the WKV6 Pallas kernel.

Shapes the kernel cannot tile raise; callers that want the jnp reference
call :func:`repro.kernels.rwkv6_wkv.ref.wkv6_ref` by name.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from .kernel import wkv6_fwd


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(
    r, k, v, w, u, state0=None, *,
    chunk: int = 128,
    interpret: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """RWKV6 recurrence. r/k/v/w: [B, T, H, N]; u: [H, N].

    Returns (out [B, T, H, N] f32, final state [B, H, N, N] f32).
    """
    b, _, h, n = r.shape
    if state0 is None:
        state0 = jnp.zeros((b, h, n, n), jnp.float32)
    return wkv6_fwd(r, k, v, w, u, state0, chunk=chunk, interpret=interpret)
