"""Pallas TPU kernel: chunked linear attention with data-dependent decay.

Serves both RWKV-6 time-mix (vector decay + bonus-u, ``mode="rwkv"``) and
Mamba-2/SSD (scalar decay broadcast to the k-dim, ``mode="ssd"``) — the same
recurrences as ``repro.models.linear_attn`` (the oracle).

TPU adaptation of the recurrent GPU kernel (DESIGN.md §4): instead of one
thread-block per head scanning tokens, the grid is
(batch, heads, T/chunk) with the chunk axis innermost and *sequential*; the
(dk × dv) state is f32 VMEM scratch carried across chunk steps. Each step does
three (C×d)·(d×C|d) MXU matmuls (intra-chunk attention, state read, state
update) on VMEM-resident tiles — chunk=64, d=64..128 keeps everything in a few
hundred KiB of VMEM.

NUMERICS CONTRACT (same as the oracle): per-step log-decay ∈ [-1, 0); with
chunk ≤ 80 the intra-chunk exponentials stay in f32 range.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rwkv_kernel(q_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, state_scr, *,
                 chunk: int, mode: str):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    qc = q_ref[0, 0].astype(jnp.float32)        # (C, dk)
    kc = k_ref[0, 0].astype(jnp.float32)
    vc = v_ref[0, 0].astype(jnp.float32)        # (C, dv)
    lw = lw_ref[0, 0].astype(jnp.float32)       # (C, dk)
    u = u_ref[0].astype(jnp.float32)            # (1, dk)

    # inclusive prefix Σ log w as a lower-triangular matmul (Mosaic has no
    # cumsum); the chunk total is needed both as a row and as a column
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    hi = jax.lax.Precision.HIGHEST
    inc = jnp.dot((row >= col).astype(jnp.float32), lw, precision=hi)
    exc = inc - lw
    tot = jnp.sum(lw, axis=0, keepdims=True)    # (1, dk)
    tot_col = jax.lax.dot_general(lw, jnp.ones((chunk, 1), jnp.float32),
                                  (((0,), (0,)), ((), ())),
                                  precision=hi)  # (dk, 1)

    q_dec = qc * jnp.exp(exc if mode == "rwkv" else inc)
    k_dec = kc * jnp.exp(-inc)
    k_tail = kc * jnp.exp(tot - inc)

    state = state_scr[...]                      # (dk, dv)
    inter = jnp.dot(q_dec, state)
    tri = (row > col).astype(jnp.float32)      # strictly causal
    att = jax.lax.dot_general(q_dec, k_dec, (((1,), (1,)), ((), ()))) * tri
    diag = jnp.sum(qc * u * kc, axis=-1, keepdims=True)
    out = inter + jnp.dot(att, vc) + diag * vc

    state_scr[...] = (state * jnp.exp(tot_col)
                      + jax.lax.dot_general(k_tail, vc, (((0,), (0,)), ((), ()))))
    o_ref[0, 0] = out.astype(o_ref.dtype)


def rwkv6_chunk(q: jax.Array, k: jax.Array, v: jax.Array, log_decay: jax.Array,
                bonus: jax.Array, *, chunk: int = 64, mode: str = "rwkv",
                interpret: bool = False) -> jax.Array:
    """q/k/lw: (B, H, T, dk); v: (B, H, T, dv); bonus: (H, dk) → (B, H, T, dv).

    T must be a multiple of ``chunk`` (ops.py pads). For ``mode="ssd"`` pass
    ``bonus=ones`` (the diag term is (q·k) with no decay). The bonus rides
    in as ``(H, 1, dk)`` so its ``(1, dk)`` tile spans the whole second-minor
    axis, as the TPU's (8, 128) tiling rule requires of a block.
    """
    b, h, t, dk = q.shape
    dv = v.shape[-1]
    n_c = t // chunk
    grid = (b, h, n_c)
    kernel = functools.partial(_rwkv_kernel, chunk=chunk, mode=mode)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dk), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, chunk, dk), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, chunk, dv), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, chunk, dk), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, dk), lambda b_, h_, c: (h_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, dv), lambda b_, h_, c: (b_, h_, c, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, t, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(q, k, v, log_decay, bonus.reshape(h, 1, dk))
