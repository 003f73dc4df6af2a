"""Pallas TPU kernel: single-token GQA decode attention over a (ring) KV cache.

This is the serving hot-spot for ``decode_32k`` / ``long_500k``: one query
token per sequence against a KV cache of up to 512k entries. TPU adaptation:

* grid = (batch, kv_heads, W/block_k); the cache-length axis is innermost and
  sequential, carrying online-softmax scratch in VMEM.
* The q_per_kv query heads of one KV head are processed *together* as a
  (q_per_kv × dh) tile so the MXU gets a real matmul instead of a per-head
  vector dot (GQA head-grouping — the TPU analogue of the CUDA warp-per-head
  layout).
* Ring-buffer validity/window masking arrives as a precomputed additive f32
  bias vector (0 / -inf per slot), blocked alongside K — no scalar prefetch
  needed and the same kernel serves append and ring caches. It is carried as
  ``(B, 1, W)`` so each ``(1, block_k)`` tile spans the whole second-minor
  axis, as the TPU's (8, 128) tiling rule requires of a block.

Memory: per grid step VMEM = block_k·dh (K) + block_k·dh (V) + q_per_kv·dh
tiles — with defaults (block_k=512, dh=128, bf16) ≈ 256 KiB, far under VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, scale: float, n_kv_blocks: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                 # (G, dh)
    k = k_ref[0, 0].astype(jnp.float32)                 # (block_k, dh)
    v = v_ref[0, 0].astype(jnp.float32)
    bias = bias_ref[0].astype(jnp.float32)              # (1, block_k)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (G, bk)
    s = s + bias

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


def flash_decode(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                 bias: jax.Array, *, block_k: int = 512,
                 interpret: bool = False) -> jax.Array:
    """q: (B, KH, G, dh); caches: (B, KH, W, dh); bias: (B, W) → (B, KH, G, dh).

    ``bias`` is 0 for valid slots and ≤ NEG_INF for invalid/out-of-window
    slots (see ops.py). W must be a multiple of block_k (ops.py pads).
    """
    b, kh, g, dh = q.shape
    w = k_cache.shape[2]
    n_k = w // block_k
    grid = (b, kh, n_k)

    kernel = functools.partial(_decode_kernel, scale=dh ** -0.5,
                               n_kv_blocks=n_k)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, dh), lambda b_, h_, j: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_k, dh), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k, dh), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b_, h_, j: (b_, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh), lambda b_, h_, j: (b_, h_, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
        interpret=interpret,
    )(q, k_cache, v_cache, bias.reshape(b, 1, w))


def _paged_decode_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                         m_scr, l_scr, acc_scr, *, scale: float,
                         block_size: int, max_blocks: int):
    """Block-table step: grid position (b, h, j) sees K/V block
    ``tbl_ref[b, j]`` of the global pool (the BlockSpec index map does the
    gather — the kernel body is the same online softmax as
    ``_decode_kernel`` with the validity mask computed in-kernel from the
    sequence length instead of a precomputed bias lane)."""
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                 # (G, dh)
    k = k_ref[0, 0].astype(jnp.float32)                 # (block_size, dh)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (G, bs)
    # token position of each row in this block; rows past the sequence
    # length are masked (covers both the ragged tail block and whole
    # padding blocks of a short table)
    pos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, (1, block_size), 1)                  # (1, bs)
    s = jnp.where(pos < len_ref[b], s, NEG_INF)

    m_prev, l_prev = m_scr[...], l_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_prev * corr + jnp.sum(p, axis=-1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jnp.dot(p, v)
    m_scr[...] = m_new

    @pl.when(j == max_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_decode_paged(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                       block_tables: jax.Array, lengths: jax.Array, *,
                       interpret: bool = False) -> jax.Array:
    """Paged decode attention: K/V gathered through per-sequence block tables.

    q: (B, KH, G, dh); pools: (num_blocks, KH, block_size, dh);
    block_tables: (B, max_blocks) int32 — physical pool block of each
    logical block (entries past the sequence's last block may point
    anywhere valid, e.g. a shared null block: the length mask zeroes their
    contribution); lengths: (B,) int32 valid tokens per sequence.
    Returns (B, KH, G, dh).

    The tables and lengths ride in as scalar-prefetch operands
    (``PrefetchScalarGridSpec``) so the K/V BlockSpec index map can address
    the pool per grid step — one compiled kernel serves every table, and two
    sequences whose tables alias the same pool blocks (shared prefixes) read
    the block out of HBM once per sequence with zero copies.
    """
    b, kh, g, dh = q.shape
    block_size = k_pool.shape[2]
    max_blocks = block_tables.shape[1]
    grid = (b, kh, max_blocks)

    kernel = functools.partial(_paged_decode_kernel, scale=dh ** -0.5,
                               block_size=block_size, max_blocks=max_blocks)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, dh),
                         lambda b_, h_, j, tbl, lens: (b_, h_, 0, 0)),
            pl.BlockSpec((1, 1, block_size, dh),
                         lambda b_, h_, j, tbl, lens: (tbl[b_, j], h_, 0, 0)),
            pl.BlockSpec((1, 1, block_size, dh),
                         lambda b_, h_, j, tbl, lens: (tbl[b_, j], h_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, dh),
                               lambda b_, h_, j, tbl, lens: (b_, h_, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, dh), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(block_tables.astype(jnp.int32), lengths.astype(jnp.int32),
      q, k_pool, v_pool)
