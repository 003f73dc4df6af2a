"""Compile rehearsals for a described TPU v5e: the Pallas kernels and the
full-width ``llama3_2_3b`` serving programs, compiled (never run) by the
TPU compiler for a chip that is not attached.

Nothing runs, so these say nothing about results or speed. They catch what
interpret mode cannot: block shapes the (8, 128) tiling rule refuses,
primitives Mosaic cannot lower, and step programs that do not fit one chip.
The topology is described inside a fixture (never at import), and every
test of this kind stays in this one file, so one test worker holds the TPU
library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.flash_decode.ops import (decode_attention_pallas,
                                            paged_decode_attention_pallas)
from repro.kernels.flash_prefill.ops import flash_attention
from repro.kernels.rwkv6_chunk.ops import linear_attention_pallas
from repro.launch.serve import CACHE_LEN, MAX_BATCH, PROMPT_LEN
from repro.models import transformer as tfm
from repro.serving.engine import RealBackend

HBM_BYTES = 16 * 2**30          # one TPU v5e
BLOCK = 16                      # the engine's default KV block size


@pytest.fixture(scope="module")
def one_chip():
    """One device of a described ``v5e:2x2``, with JAX's persistent cache
    off (a TPU compile written to it cannot be read back without a chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding):
    def make(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return make


# ------------------------------------------------------------------ kernels
def _kernel_case(name, s):
    """(jitted kernel, argument shapes) at Llama 3.2 3B widths (attention)
    and RWKV6-7B widths (linear attention)."""
    b, h, kh, dh, w = MAX_BATCH, 24, 8, 128, CACHE_LEN
    i32 = jnp.int32
    if name == "flash_decode":
        return decode_attention_pallas, (
            s((b, h, dh)), s((b, w, kh, dh)), s((b, w, kh, dh)), s((), i32))
    if name == "flash_decode_paged":
        n_blocks = b * w // BLOCK + 1
        return paged_decode_attention_pallas, (
            s((b, h, dh)), s((n_blocks, kh, BLOCK, dh)),
            s((n_blocks, kh, BLOCK, dh)), s((b, w // BLOCK), i32),
            s((b,), i32))
    if name == "flash_prefill":
        return flash_attention, (s((1, h, w, dh)), s((1, kh, w, dh)),
                                 s((1, kh, w, dh)))
    rh, rd, t = 64, 64, PROMPT_LEN                 # rwkv6_7b time-mix heads
    f32 = jnp.float32
    return linear_attention_pallas, (
        s((1, rh, t, rd)), s((1, rh, t, rd)), s((1, rh, t, rd)),
        s((1, rh, t, rd), f32), s((rh, rd), f32))


@pytest.mark.parametrize("name", ["flash_decode", "flash_decode_paged",
                                  "flash_prefill", "rwkv6_chunk"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name, _spec(one_chip))
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ----------------------------------------------------- full-width programs
@pytest.fixture(scope="module")
def served(one_chip):
    """The paged engine's jitted programs for full-width ``llama3_2_3b`` at
    the launcher's sizes, and the weights' shapes — no array is made."""
    cfg = get_config("llama3_2_3b")
    backend = RealBackend(cfg, None, max_batch=MAX_BATCH, cache_len=CACHE_LEN,
                          prompt_len=PROMPT_LEN, paged=True)
    backend._paged_programs(BLOCK)
    s = _spec(one_chip)
    params = jax.tree.map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(lambda k: tfm.init_params(k, cfg),
                       jax.random.PRNGKey(0)))
    return cfg, backend, params, s


def _program_case(name, cfg, be, params, s):
    b, w, p = MAX_BATCH, CACHE_LEN, PROMPT_LEN
    L, kh, dh = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    pool = s((b * w // BLOCK + 1, L, BLOCK, kh, dh))
    i32 = jnp.int32
    key = s((2,), jnp.uint32)
    tokens = s((b, 1), i32)
    if name == "decode_paged":
        return be._decode_paged, (params, pool, pool, tokens, s((b,), i32),
                                  s((b, w // BLOCK), i32), s((b,), i32), key)
    if name == "prefill_bucket":
        return be._prefill_bucket, (params, s((b, p), i32), s((b,), i32), key)
    if name == "extend_chunk_paged":
        return be._extend_chunk_paged, (
            params, pool, pool, tokens, s((b, 256), i32), s((b,), i32),
            s((b, w // BLOCK), i32), s((b,), i32), s((b,), jnp.bool_), key)
    lane = s((L, b, w, kh, dh))
    return be._place_paged, (pool, pool, lane, lane, tokens, s((b,), i32),
                             s((b, p // BLOCK), i32), s((b,), i32))


@pytest.mark.parametrize("name", ["decode_paged", "prefill_bucket",
                                  "extend_chunk_paged", "place_paged"])
def test_full_width_program_fits_one_v5e(served, name):
    cfg, backend, params, s = served
    fn, args = _program_case(name, cfg, backend, params, s)
    mem = fn.lower(*args).compile().memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert need <= HBM_BYTES, f"{name} needs {need / 2**30:.2f} GiB"
