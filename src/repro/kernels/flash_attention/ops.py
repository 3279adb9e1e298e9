"""Jit-ready wrapper for the flash-attention Pallas kernel.

Accepts the model's native [B, S, H, hd] layout, transposes to the
kernel's heads-first tiling layout and picks MXU-aligned block sizes.
Shapes the kernel cannot tile (sequence lengths with no 128-multiple
block) raise; callers that want the jnp reference call
:func:`repro.kernels.flash_attention.ref.flash_attention_ref` by name.

The default is the compiled Mosaic kernel; tests on the CPU pass
``interpret=True`` (Pallas executes the kernel body in Python, with
identical semantics).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .kernel import flash_attention_fwd


def _pick_block(s: int, preferred: int) -> int:
    for b in (preferred, 512, 256, 128):
        if b <= s and s % b == 0:
            return b
    raise ValueError(
        f"sequence length {s} has no 128-multiple block; use flash_attention_ref"
    )


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "logit_softcap", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jnp.ndarray,  # [B, Sq, H, hd]
    k: jnp.ndarray,  # [B, Sk, KVH, hd]
    v: jnp.ndarray,  # [B, Sk, KVH, hd]
    *,
    causal: bool = True,
    window: Optional[int] = None,
    logit_softcap: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jnp.ndarray:
    qt = jnp.swapaxes(q, 1, 2)  # [B, H, Sq, hd]
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_fwd(
        qt, kt, vt,
        causal=causal, window=window, logit_softcap=logit_softcap,
        block_q=_pick_block(qt.shape[2], block_q),
        block_k=_pick_block(kt.shape[2], block_k),
        interpret=interpret,
    )
    return jnp.swapaxes(out, 1, 2)
