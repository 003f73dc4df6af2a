"""Jitted public wrappers for flash_decode (contiguous ring lanes) and
flash_decode_paged (block-table-gathered pool).

``decode_attention_pallas`` mirrors the signature of
``repro.models.attention.decode_attention`` (its XLA twin) so the two are
drop-in interchangeable behind the model's ``attn_impl`` switch.
``paged_decode_attention_pallas`` is the block-table analogue over a global
``(num_blocks, KH, block_size, dh)`` KV pool.

Both compile for the TPU by default; pass ``interpret=True`` to run them in
the Pallas interpreter (how the CPU tests run them).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_decode.kernel import (NEG_INF, flash_decode,
                                               flash_decode_paged)
from repro.models.attention import ring_slot_positions


@functools.partial(jax.jit, static_argnames=("window", "block_k", "interpret"))
def decode_attention_pallas(q: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, pos: jax.Array, *,
                            window: Optional[int] = None, block_k: int = 512,
                            interpret: bool = False) -> jax.Array:
    """q: (B, H, dh); caches: (B, W, KH, dh); pos: scalar → (B, H, dh)."""
    b, h, dh = q.shape
    w, kh = k_cache.shape[1], k_cache.shape[2]
    g = h // kh

    slot_pos = ring_slot_positions(jnp.asarray(pos) + 1, w)   # (W,)
    valid = slot_pos >= 0
    if window is not None:
        valid &= pos - slot_pos < window
    valid &= slot_pos <= pos
    bias = jnp.where(valid, 0.0, NEG_INF).astype(jnp.float32)
    bias = jnp.broadcast_to(bias[None], (b, w))

    qg = q.reshape(b, kh, g, dh)
    kc = k_cache.transpose(0, 2, 1, 3)                        # (B, KH, W, dh)
    vc = v_cache.transpose(0, 2, 1, 3)

    bk = min(block_k, w)
    pad = (-w) % bk
    if pad:
        kc = jnp.pad(kc, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vc = jnp.pad(vc, ((0, 0), (0, 0), (0, pad), (0, 0)))
        bias = jnp.pad(bias, ((0, 0), (0, pad)), constant_values=NEG_INF)

    out = flash_decode(qg, kc, vc, bias, block_k=bk, interpret=interpret)
    return out.reshape(b, h, dh)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q: jax.Array, k_pool: jax.Array,
                                  v_pool: jax.Array, block_tables: jax.Array,
                                  lengths: jax.Array, *,
                                  interpret: bool = False) -> jax.Array:
    """q: (B, H, dh); pools: (num_blocks, KH, block_size, dh);
    block_tables: (B, max_blocks) int32; lengths: (B,) int32 → (B, H, dh).

    GQA folding only — masking lives in the kernel (rows at token positions
    ``>= lengths[b]`` contribute nothing, so table padding entries may point
    at any valid pool block)."""
    b, h, dh = q.shape
    kh = k_pool.shape[1]
    qg = q.reshape(b, kh, h // kh, dh)
    out = flash_decode_paged(qg, k_pool, v_pool, block_tables, lengths,
                             interpret=interpret)
    return out.reshape(b, h, dh)
