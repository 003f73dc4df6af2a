"""Pallas TPU kernel: causal GQA flash attention (prefill / training).

TPU adaptation (DESIGN.md §4): rather than porting the CUDA warp layout, the
kernel tiles for VMEM and the MXU —

* grid = (batch, q_heads, Sq/block_q, Skv/block_k); the KV axis is the
  innermost, *sequential* ("arbitrary") dimension so the online-softmax
  scratch accumulators persist across KV tiles in VMEM.
* BlockSpecs stage (block_q × dh) Q tiles and (block_k × dh) K/V tiles
  HBM→VMEM; block sizes default to 128 so the MXU sees 128-aligned matmuls.
* GQA is expressed in the K/V index_map (kv_head = q_head // q_per_kv) — no
  materialized head broadcast.
* Softmax statistics (m, l) and the output accumulator are f32 VMEM scratch.
* Fully-masked causal tiles are skipped with pl.when (upper-triangle pruning).

Validated in interpret mode against ``ref.py`` (this container is CPU-only;
TPU is the compile target).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, block_q: int, block_k: int, seq_q: int,
                  seq_k: int, causal: bool, window: Optional[int],
                  n_kv_blocks: int):
    i = pl.program_id(2)      # q block
    j = pl.program_id(3)      # kv block (sequential)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal pruning: this tile contributes iff some k_idx <= some q_idx
    live = jnp.asarray(True)
    if causal:
        live = j * block_k <= i * block_q + block_q - 1
    if window is not None:
        # tile dead if even the newest k is older than the oldest q's window
        live = jnp.logical_and(
            live, i * block_q - (j * block_k + block_k - 1) < window)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # (block_q, dh)
        k = k_ref[0, 0].astype(jnp.float32)            # (block_k, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        q_idx = i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_idx = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        mask = (k_idx < seq_k) & (q_idx < seq_q)
        if causal:
            mask &= k_idx <= q_idx
        if window is not None:
            mask &= q_idx - k_idx < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jnp.dot(p, v)
        m_scr[...] = m_new

    @pl.when(j == n_kv_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: Optional[int] = None,
                  block_q: int = 128, block_k: int = 128,
                  true_q: Optional[int] = None, true_k: Optional[int] = None,
                  interpret: bool = False) -> jax.Array:
    """q: (B, H, Sq, dh); k/v: (B, KH, Skv, dh) → (B, H, Sq, dh).

    Sq/Skv must be multiples of the block sizes (ops.py pads).
    """
    b, h, sq, dh = q.shape
    kh, skv = k.shape[1], k.shape[2]
    qpk = h // kh
    n_q, n_k = sq // block_q, skv // block_k
    grid = (b, h, n_q, n_k)

    kernel = functools.partial(
        _flash_kernel, scale=dh ** -0.5, block_q=block_q, block_k=block_k,
        seq_q=true_q or sq, seq_k=true_k or skv, causal=causal,
        window=window, n_kv_blocks=n_k)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, dh), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, dh), lambda b_, h_, i, j: (b_, h_ // qpk, j, 0)),
            pl.BlockSpec((1, 1, block_k, dh), lambda b_, h_, i, j: (b_, h_ // qpk, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, dh), lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
