"""Pallas kernel microbenchmarks (interpret mode — functional timing only on
CPU; the BlockSpec/VMEM structure is the TPU deliverable, see kernels/*)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.kernels.flash_decode.ops import decode_attention_pallas
from repro.kernels.flash_prefill.ops import flash_attention
from repro.kernels.rwkv6_chunk.ops import linear_attention_pallas
from repro.models.attention import attention_chunked, decode_attention


def _time(fn, *args, iters=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run() -> None:
    key = jax.random.PRNGKey(0)
    print("# kernel microbench (CPU interpret mode) — name,us_per_call,derived")

    # flash prefill vs XLA chunked reference
    b, h, kh, s, dh = 1, 8, 2, 512, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (b, h, s, dh), jnp.bfloat16)
    k = jax.random.normal(ks[1], (b, kh, s, dh), jnp.bfloat16)
    v = jax.random.normal(ks[2], (b, kh, s, dh), jnp.bfloat16)
    us = _time(lambda *a: flash_attention(*a, interpret=True), q, k, v)
    emit("flash_prefill_pallas_interp_b1h8s512", us,
         f"{2 * 2 * b * h * s * s * dh / (us / 1e6) / 1e9:.2f}GFLOP/s-equiv")
    qb = q.transpose(0, 2, 1, 3)
    kb = k.transpose(0, 2, 1, 3)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    us = _time(lambda: attention_chunked(qb, kb, v.transpose(0, 2, 1, 3),
                                         pos, pos))
    emit("flash_prefill_xla_chunked_b1h8s512", us, "XLA twin")

    # decode over 8k cache
    w = 8192
    q1 = jax.random.normal(ks[0], (4, 8, 128), jnp.bfloat16)
    kc = jax.random.normal(ks[1], (4, w, 2, 128), jnp.bfloat16)
    vc = jax.random.normal(ks[2], (4, w, 2, 128), jnp.bfloat16)
    us = _time(lambda: decode_attention_pallas(q1, kc, vc, w - 1,
                                                interpret=True))
    emit("flash_decode_pallas_interp_b4w8192", us, "ring-masked")
    us = _time(lambda: decode_attention(q1, kc, vc, w - 1))
    emit("flash_decode_xla_b4w8192", us, "XLA twin")

    # rwkv6 chunked
    q2 = jax.random.normal(ks[0], (1, 8, 1024, 64))
    k2 = jax.random.normal(ks[1], (1, 8, 1024, 64))
    v2 = jax.random.normal(ks[2], (1, 8, 1024, 64))
    lw = -jax.nn.sigmoid(jax.random.normal(ks[0], (1, 8, 1024, 64)))
    u = jnp.zeros((8, 64))
    us = _time(lambda: linear_attention_pallas(q2, k2, v2, lw, u,
                                                interpret=True))
    emit("rwkv6_chunk_pallas_interp_t1024", us, "chunk=64")


if __name__ == "__main__":
    run()
