"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracles,
swept over shapes and dtypes (deliverable (c))."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_prefill.ops import flash_attention
from repro.kernels.flash_prefill.ref import flash_prefill_ref
from repro.kernels.flash_decode.ops import (decode_attention_pallas,
                                            paged_decode_attention_pallas)
from repro.kernels.flash_decode.ref import (flash_decode_paged_ref,
                                            flash_decode_ref)
from repro.kernels.rwkv6_chunk.ops import linear_attention_pallas
from repro.kernels.rwkv6_chunk.ref import rwkv6_recurrent_ref
from repro.models.attention import decode_attention
from repro.models.linear_attn import chunked_linear_attention


def _tol(dt):
    return dict(atol=2e-2, rtol=2e-2) if dt == jnp.bfloat16 else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("b,h,kh,sq,skv,dh", [
    (2, 4, 2, 128, 128, 64),
    (1, 8, 2, 256, 256, 128),
    (2, 4, 4, 96, 96, 64),          # ragged → padding path
    (1, 4, 1, 64, 64, 32),          # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_prefill_matches_ref(b, h, kh, sq, skv, dh, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, h, sq, dh), dtype)
    k = jax.random.normal(ks[1], (b, kh, skv, dh), dtype)
    v = jax.random.normal(ks[2], (b, kh, skv, dh), dtype)
    out = flash_attention(q, k, v, interpret=True)
    ref = flash_prefill_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_prefill_sliding_window():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 4, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    out = flash_attention(q, k, v, window=64, interpret=True)
    ref = flash_prefill_ref(q, k, v, window=64)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("b,h,kh,w,dh,pos,win", [
    (2, 8, 2, 512, 64, 300, None),
    (1, 4, 4, 1024, 128, 800, None),
    (2, 4, 2, 256, 64, 700, 128),    # ring buffer wrapped
    (1, 8, 8, 300, 64, 150, None),   # ragged W → padding
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_matches_model(b, h, kh, w, dh, pos, win, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (b, h, dh), dtype)
    kc = jax.random.normal(ks[1], (b, w, kh, dh), dtype)
    vc = jax.random.normal(ks[2], (b, w, kh, dh), dtype)
    out = decode_attention_pallas(q, kc, vc, pos, window=win,
                                  interpret=True)
    ref = decode_attention(q, kc, vc, pos, window=win)  # XLA twin in the model
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def _paged_setup(seed, b, kh, bs, mb, dh, n_blocks, dtype):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    k_pool = jax.random.normal(ks[0], (n_blocks, kh, bs, dh), dtype)
    v_pool = jax.random.normal(ks[1], (n_blocks, kh, bs, dh), dtype)
    # shuffled tables, with deliberate cross-sequence aliasing: every
    # sequence's first block is block 0 (a shared prefix in pool terms)
    rng = np.random.default_rng(seed)
    tables = np.stack([rng.permutation(n_blocks)[:mb] for _ in range(b)])
    tables[:, 0] = 0
    return ks[2], k_pool, v_pool, jnp.asarray(tables, jnp.int32)


@pytest.mark.parametrize("b,h,kh,bs,mb,dh", [
    (2, 8, 2, 16, 8, 64),            # GQA group 4
    (1, 4, 4, 32, 4, 128),           # MHA
    (3, 4, 1, 16, 6, 32),            # MQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_paged_matches_ref(b, h, kh, bs, mb, dh, dtype):
    kq, k_pool, v_pool, tables = _paged_setup(7, b, kh, bs, mb, dh, 64, dtype)
    q = jax.random.normal(kq, (b, h, dh), dtype)
    # ragged: one full sequence, the rest at assorted partial lengths
    lengths = jnp.asarray([mb * bs - (i * 7) % (mb * bs - 1) if i else mb * bs
                           for i in range(b)], jnp.int32)
    out = paged_decode_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                        interpret=True)
    g = h // kh
    ref = flash_decode_paged_ref(q.reshape(b, kh, g, dh), k_pool, v_pool,
                                 tables, lengths).reshape(b, h, dh)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_paged_linear_table_matches_contiguous(dtype):
    """With the identity block table, the paged kernel must agree with the
    contiguous flash_decode on the same (gathered) cache — the table
    indirection itself must not perturb the math."""
    b, h, kh, bs, mb, dh = 2, 8, 2, 16, 8, 64
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    k_pool = jax.random.normal(ks[0], (b * mb, kh, bs, dh), dtype)
    v_pool = jax.random.normal(ks[1], (b * mb, kh, bs, dh), dtype)
    q = jax.random.normal(ks[2], (b, h, dh), dtype)
    tables = jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb)
    lengths = jnp.asarray([mb * bs, mb * bs - 37], jnp.int32)
    paged = paged_decode_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                          interpret=True)
    # contiguous layout: (b, w, kh, dh) cache holding the same rows
    kc = jnp.moveaxis(k_pool.reshape(b, mb, kh, bs, dh), 2, 1) \
        .reshape(b, kh, mb * bs, dh)
    vc = jnp.moveaxis(v_pool.reshape(b, mb, kh, bs, dh), 2, 1) \
        .reshape(b, kh, mb * bs, dh)
    for i in range(b):
        # contiguous pos attends slots [0, pos] inclusive; paged lengths
        # count entries — pos = length - 1 views the same rows
        row = decode_attention_pallas(
            q[i:i + 1], jnp.moveaxis(kc[i:i + 1], 1, 2),
            jnp.moveaxis(vc[i:i + 1], 1, 2), int(lengths[i]) - 1,
            interpret=True)
        np.testing.assert_allclose(np.asarray(paged[i], np.float32),
                                   np.asarray(row[0], np.float32),
                                   **_tol(dtype))


def test_flash_decode_paged_aliased_tables_share_exactly():
    """Two sequences whose tables alias the same leading blocks and have the
    same length produce bitwise-identical outputs for identical queries —
    the zero-copy sharing guarantee the serving hit path relies on."""
    b, h, kh, bs, mb, dh = 2, 4, 2, 16, 4, 64
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    k_pool = jax.random.normal(ks[0], (32, kh, bs, dh))
    v_pool = jax.random.normal(ks[1], (32, kh, bs, dh))
    q1 = jax.random.normal(ks[2], (1, h, dh))
    q = jnp.concatenate([q1, q1])                 # same query both lanes
    shared = [3, 9, 5]
    tables = jnp.asarray([shared + [11], shared + [20]], jnp.int32)
    lengths = jnp.asarray([3 * bs, 3 * bs], jnp.int32)  # tail block masked
    out = paged_decode_attention_pallas(q, k_pool, v_pool, tables, lengths,
                                        interpret=True)
    assert np.array_equal(np.asarray(out[0]), np.asarray(out[1]))


@pytest.mark.parametrize("mode", ["rwkv", "ssd"])
@pytest.mark.parametrize("b,h,t,dk,dv", [
    (2, 4, 128, 64, 64),
    (1, 2, 200, 32, 64),             # ragged T → padding
    (1, 1, 64, 16, 16),
])
def test_rwkv6_chunk_vs_recurrent(mode, b, h, t, dk, dv):
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    q = jax.random.normal(ks[0], (b, h, t, dk))
    k = jax.random.normal(ks[1], (b, h, t, dk))
    v = jax.random.normal(ks[2], (b, h, t, dv))
    lw_dim = dk if mode == "rwkv" else 1
    lw = -jax.nn.sigmoid(jax.random.normal(ks[3], (b, h, t, lw_dim)))
    u = (jax.random.normal(ks[4], (h, dk)) * 0.1 if mode == "rwkv"
         else jnp.ones((h, dk)))
    out = linear_attention_pallas(q, k, v, lw, u if mode == "rwkv" else None,
                                  mode=mode, interpret=True)
    ref = rwkv6_recurrent_ref(q, k, v, lw, u, mode=mode)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-4, rtol=5e-4)


def test_model_chunked_linear_attn_vs_recurrent():
    """The model-level chunked path must agree with the recurrence too."""
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    b, h, t, d = 2, 2, 128, 32
    q, k = (jax.random.normal(ks[i], (b, h, t, d)) for i in range(2))
    v = jax.random.normal(ks[2], (b, h, t, d))
    lw = -jax.nn.sigmoid(jax.random.normal(ks[3], (b, h, t, d)))
    u = jax.random.normal(ks[4], (h, d)) * 0.1
    out, _ = chunked_linear_attention(q, k, v, lw, bonus=u, mode="rwkv",
                                      chunk_size=32)
    ref = rwkv6_recurrent_ref(q, k, v, lw, u, mode="rwkv")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=5e-4, rtol=5e-4)
