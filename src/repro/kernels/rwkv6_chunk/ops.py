"""Jitted public wrapper for rwkv6_chunk (padding + scalar-decay broadcast)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6_chunk.kernel import rwkv6_chunk


@functools.partial(jax.jit, static_argnames=("chunk", "mode", "interpret"))
def linear_attention_pallas(q, k, v, log_decay, bonus=None, *, chunk: int = 64,
                            mode: str = "rwkv", interpret: bool = False):
    """Drop-in twin of models.linear_attn.chunked_linear_attention (output
    only — state handoff stays in the XLA path). Pads T to the chunk size and
    broadcasts scalar SSD decay across the k-dim."""
    b, h, t, dk = q.shape
    lw = jnp.broadcast_to(log_decay, (b, h, t, dk))
    if bonus is None:
        bonus = jnp.ones((h, dk), jnp.float32)
    pad = (-t) % chunk
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        q, k, v = (jnp.pad(x, widths) for x in (q, k, v))
        lw = jnp.pad(lw, widths)
    out = rwkv6_chunk(q, k, v, lw, bonus, chunk=chunk, mode=mode,
                      interpret=interpret)
    return out[:, :, :t]
