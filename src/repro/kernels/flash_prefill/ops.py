"""Jitted public wrapper for flash_prefill: padding + layout handling."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_prefill.kernel import flash_prefill


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jax.Array:
    """(B, H, Sq, dh) × (B, KH, Skv, dh) → (B, H, Sq, dh), padded to blocks."""
    sq, skv = q.shape[2], k.shape[2]
    bq = min(block_q, max(8, 1 << (sq - 1).bit_length()))
    bk = min(block_k, max(8, 1 << (skv - 1).bit_length()))
    qp = _pad_to(q, 2, bq)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    out = flash_prefill(qp, kp, vp, causal=causal, window=window,
                        block_q=bq, block_k=bk, true_q=sq, true_k=skv,
                        interpret=interpret)
    return out[:, :, :sq]
