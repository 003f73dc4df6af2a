"""Paged KV serving: the real engine with block-table-pooled KV must be an
*observable no-op* versus contiguous lanes — greedy outputs bit-identical in
every mode combination — while the hit path stops copying KV entirely
(zero-copy block aliasing) and incremental reservation admits more, recovers
from grow failures by preemption, and never deadlocks.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.scheduler.policies import fcfs
from repro.core.scheduler.request import Request
from repro.core.scheduler.scheduler import Scheduler
from repro.models import transformer as tfm
from repro.serving.engine import Engine, RealBackend
from repro.serving.kv_cache import BlockAllocator


@pytest.fixture(scope="module")
def setup():
    from repro.models import transformer as tfm

    cfg = get_smoke_config("llama3_2_3b").replace(dtype="float32",
                                                  vocab_size=2048)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _words(n, tag):
    return " ".join(f"{tag}w{j}" for j in range(n))


def _run(cfg, params, paged, *, chunk=None, caching=False, reservation="full",
         allocator=None, cache_len=96, prompt_len=32, max_batch=4, reqs=None):
    if reqs is None:
        shared = _words(24, "sys")
        reqs = [Request(i, shared + " " + _words(6, f"u{i}"), 0.0, 32, 4 + i)
                for i in range(4)]
        reqs += [Request(10 + i, _words(10, f"solo{i}"), 0.0, 32, 5)
                 for i in range(2)]
    eng = Engine(cfg, params, Scheduler(policy=fcfs(), max_batch=max_batch),
                 cache_len=cache_len, prompt_len=prompt_len, paged=paged,
                 prefill_chunk_tokens=chunk, prefix_caching=caching,
                 kv_reservation=reservation, allocator=allocator,
                 record_tokens=True)
    eng.submit(reqs)
    fin = eng.run()
    assert len(fin) == len(reqs)
    return {r.req_id: r.generated_tokens for r in fin}, eng


@pytest.mark.parametrize("chunk,caching", [
    (None, False),            # plain bucketed admission + decode
    (16, False),              # chunked prefill
    (None, True),             # prefix caching (hit resumes mid-prompt)
    (16, True),               # both composed
])
def test_paged_outputs_bit_identical_to_contiguous(setup, chunk, caching):
    """Acceptance: greedy outputs are bit-identical paged vs contiguous,
    including prefix-cache hits and chunked prefill."""
    cfg, params = setup
    contig, _ = _run(cfg, params, False, chunk=chunk, caching=caching)
    paged, eng = _run(cfg, params, True, chunk=chunk, caching=caching)
    assert paged == contig
    assert eng.backend.paged
    assert eng.allocator.used_blocks == 0          # everything released


def test_paged_prefix_hit_copies_zero_tokens(setup):
    """The paged hit path aliases pool blocks into the new request's table:
    ``prefix_installs`` counts the claims, ``prefix_tokens_copied`` stays 0
    (contiguous mode copies the fragments instead)."""
    cfg, params = setup
    shared = _words(30, "sys")

    def two_phase(paged):
        eng = Engine(cfg, params, Scheduler(policy=fcfs(), max_batch=4),
                     cache_len=96, prompt_len=64, paged=paged,
                     prefix_caching=True, record_tokens=True)
        eng.submit([Request(0, shared + " donor", 0.0, 40, 4)])
        eng.run()
        eng.submit([Request(10 + i, shared + " " + _words(4, f"u{i}"),
                            0.0, 40, 4) for i in range(3)])
        eng.run()
        assert len(eng.finished) == 4
        return eng

    off = two_phase(False)
    on = two_phase(True)
    assert ({r.req_id: r.generated_tokens for r in on.finished}
            == {r.req_id: r.generated_tokens for r in off.finished})
    assert on.backend.prefix_installs == off.backend.prefix_installs == 3
    assert off.backend.prefix_tokens_copied > 0    # fragment-store copies
    assert on.backend.prefix_tokens_copied == 0    # zero-copy aliasing
    hits = {r.req_id: r.cached_prefix_tokens for r in on.finished}
    assert hits[0] == 0 and all(hits[10 + i] > 0 for i in range(3))


def test_incremental_reservation_grow_preempts_and_recovers(setup):
    """Under a KV budget too small for every admitted request's full demand,
    incremental reservation over-admits, hits decode-time grow failures,
    preempts deterministically, and still finishes every request with
    correct token counts and a clean allocator."""
    cfg, params = setup
    reqs = [Request(i, _words(8, f"r{i}"), 0.0, 16, 24) for i in range(6)]
    outs, eng = _run(cfg, params, True, reservation="incremental",
                     allocator=BlockAllocator(8, 16), cache_len=48,
                     prompt_len=16, max_batch=6, reqs=reqs)
    fin = eng.finished
    assert all(r.tokens_done == r.true_length for r in fin)
    assert sum(r.grow_failures or 0 for r in fin) > 0
    assert sum(r.grow_preemptions or 0 for r in fin) > 0
    assert sum(r.preempt_count for r in fin) > 0   # victims really evicted
    assert eng.allocator.used_blocks == 0

    # same workload, same budget, full reservation: outputs still identical
    # (admission order may differ; token streams must not)
    reqs2 = [Request(i, _words(8, f"r{i}"), 0.0, 16, 24) for i in range(6)]
    outs_full, eng_full = _run(cfg, params, True, reservation="full",
                               allocator=BlockAllocator(8, 16), cache_len=48,
                               prompt_len=16, max_batch=6, reqs=reqs2)
    assert all(r.grow_failures is None for r in eng_full.finished)


def test_paged_recompute_preemption_matches_contiguous(setup):
    """Preemption + re-admission (recompute semantics) under paged KV:
    outputs still bit-identical to the contiguous engine on the same
    budget-constrained workload."""
    cfg, params = setup

    def constrained(paged):
        reqs = [Request(i, _words(8, f"p{i}"), 0.0, 16, 12) for i in range(5)]
        return _run(cfg, params, paged, allocator=BlockAllocator(6, 16),
                    cache_len=32, prompt_len=16, max_batch=5, reqs=reqs)

    contig, ec = constrained(False)
    paged, ep = constrained(True)
    assert paged == contig
    assert ep.allocator.used_blocks == 0


def test_paged_rejects_unbounded_allocator(setup):
    cfg, params = setup
    with pytest.raises(ValueError, match="bounded"):
        Engine(cfg, params, Scheduler(policy=fcfs(), max_batch=2),
               cache_len=64, prompt_len=16, paged=True,
               allocator=BlockAllocator.unbounded(16))


def test_paged_auto_default_skips_recurrent_families():
    """``paged=None`` auto-detects: attention families page, recurrent
    families keep contiguous lanes (their cache is not block-structured)."""
    from repro.models import transformer as tfm

    cfg = get_smoke_config("rwkv6_7b").replace(dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    eng = Engine(cfg, params, Scheduler(policy=fcfs(), max_batch=2),
                 cache_len=64, prompt_len=16)
    assert not eng.backend.paged
    with pytest.raises(ValueError, match="attention-family"):
        Engine(cfg, params, Scheduler(policy=fcfs(), max_batch=2),
               cache_len=64, prompt_len=16, paged=True)


def test_paged_mode_holds_only_the_pool(setup):
    """Paged mode keeps no contiguous per-slot cache: the pool (plus its
    null block) is the only KV it allocates; contiguous mode keeps lanes."""
    cfg, params = setup
    sched = lambda: Scheduler(policy=fcfs(), max_batch=2)  # noqa: E731
    paged = Engine(cfg, params, sched(), cache_len=64, prompt_len=16)
    assert paged.backend.paged and paged.backend.cache is None
    assert paged.backend.k_pool.shape[0] == paged.allocator.total_blocks + 1
    lanes = Engine(cfg, params, sched(), cache_len=64, prompt_len=16,
                   paged=False)
    assert lanes.backend.cache["k"].shape[:1] == (2,)


def test_backend_commits_its_arrays_to_its_device(setup):
    """A backend given a device keeps its params, KV pool and slot tokens
    there and serves from it — what lets replicas share one process."""
    cfg, params = setup
    dev = jax.devices()[-1]
    eng = Engine(cfg, params, Scheduler(policy=fcfs(), max_batch=2),
                 cache_len=64, prompt_len=16, device=dev, record_tokens=True)
    be = eng.backend
    eng.submit([Request(i, _words(8, f"d{i}"), 0.0, 8, 3) for i in range(2)])
    assert len(eng.run()) == 2
    for a in (be.k_pool, be.v_pool, be.slot_tokens,
              *jax.tree.leaves(be.params)):
        assert a.devices() == {dev}


# ------------------------------------------- decode straight from the pool
BS = 16                          # block size of the direct-program tests


def _pools(cfg, n_blocks, seed=1):
    """Random K and V pools (n_blocks, L, BS, KH, dh): rows a lane reads past
    ``pos`` hold garbage that the mask has to hide."""
    shape = (n_blocks, cfg.num_layers, BS, cfg.num_kv_heads, cfg.head_dim)
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kk, shape, cfg.dtype),
            jax.random.normal(kv, shape, cfg.dtype))


def _lane(pool, table):
    """A table's blocks as one contiguous (L, 1, W, KH, dh) cache lane."""
    _, L, bs, kh, dh = pool.shape
    return jnp.moveaxis(pool[table], 1, 0).reshape(L, 1, len(table) * bs,
                                                   kh, dh)


@pytest.mark.parametrize("pos", [2 * BS + 5, 3 * BS],
                         ids=["inside_block", "block_boundary"])
@pytest.mark.parametrize("name", ["llama3_2_3b", "olmoe_1b_7b",
                                  "qwen2_vl_72b"])
def test_decode_step_paged_matches_decode_step_on_a_gathered_lane(name, pos):
    """DENSE, MOE and VLM (mrope): each layer reading its lane from the pool
    gives the logits and the new K/V rows of ``decode_step`` over the same
    table gathered into a lane, bit for bit."""
    cfg = get_smoke_config(name).replace(dtype="float32")
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    k_pool, v_pool = _pools(cfg, 9)
    table = jnp.asarray([4, 0, 7, 2, 5], jnp.int32)
    w = len(table) * BS
    token = jnp.asarray([11], jnp.int32)

    logits, k_rows, v_rows = jax.jit(
        lambda p, k, v, t, tok: tfm.decode_step_paged(p, cfg, k, v, t, pos,
                                                      tok))(
        params, k_pool, v_pool, table, token)
    cache = {"k": _lane(k_pool, table), "v": _lane(v_pool, table),
             "pos": jnp.asarray(pos, jnp.int32)}
    ref_logits, ref_cache = jax.jit(
        lambda p, c, tok: tfm.decode_step(p, cfg, c, tok))(
        params, cache, token[None])

    assert k_rows.shape == (cfg.num_layers, cfg.num_kv_heads, cfg.head_dim)
    assert np.array_equal(logits, ref_logits[0])
    assert np.array_equal(k_rows, ref_cache["k"][:, 0, pos % w])
    assert np.array_equal(v_rows, ref_cache["v"][:, 0, pos % w])


def _programs(cfg, params, max_batch, cache_len):
    be = RealBackend(cfg, params, max_batch=max_batch, cache_len=cache_len,
                     prompt_len=16, paged=True)
    be._paged_programs(BS)
    return be


def test_decode_writes_one_row_per_layer_and_sequence(setup):
    """One paged decode step changes the pools only at row ``pos % bs`` of
    block ``table[(pos % W) // bs]`` of each real row, with the rows
    ``decode_step_paged`` computes; a padding row replaying a real one and
    a row on the all-null table leave every other block bit-equal."""
    cfg, params = setup
    mb, null = 4, 12
    w = mb * BS
    be = _programs(cfg, params, max_batch=4, cache_len=w)
    k_pool, v_pool = _pools(cfg, null + 1)
    tables = np.asarray([[3, 7, 1, 9],            # real, pos inside block 1
                         [0, 5, 10, 2],           # real, pos at a boundary
                         [3, 7, 1, 9],            # padding: replays row 0
                         [null] * mb], np.int32)  # unreserved: null table
    poss = np.asarray([2 * BS + 5, 3 * BS, 2 * BS + 5, BS + 5], np.int32)
    idx = np.asarray([0, 1, 0, 2], np.int32)
    slot_tokens = jnp.asarray([[5], [9], [13], [0]], jnp.int32)
    key = jax.random.PRNGKey(3)

    _, k_new, v_new = be._decode_paged(params, k_pool, v_pool, slot_tokens,
                                       idx, tables, poss, key)

    changed = np.zeros(k_pool.shape[:1] + k_pool.shape[2:3], bool)
    for r in (0, 1, 3):
        changed[tables[r][(poss[r] % w) // BS], poss[r] % BS] = True
    for old, new in ((k_pool, k_new), (v_pool, v_new)):
        keep = ~changed[:, None, :, None, None] & np.ones(old.shape, bool)
        assert np.array_equal(np.asarray(new)[keep], np.asarray(old)[keep])
    # one row alone: its matmuls round apart from the batch's in the last
    # bits on the CPU, so the written rows are compared within float32 noise
    step = jax.jit(lambda t, p, tok: tfm.decode_step_paged(
        params, cfg, k_pool, v_pool, t, p, tok))
    for r in (0, 1):
        _, k_rows, v_rows = step(tables[r], poss[r], slot_tokens[idx[r]])
        dest, row = tables[r][(poss[r] % w) // BS], poss[r] % BS
        assert np.allclose(k_new[dest, :, row], k_rows, rtol=1e-5, atol=1e-5)
        assert np.allclose(v_new[dest, :, row], v_rows, rtol=1e-5, atol=1e-5)
        assert not np.allclose(k_pool[dest, :, row], k_rows, atol=1e-2)


_HLO_ARRAY = re.compile(r"\b(?:pred|[suf](?:8|16|32|64)|bf16)\[([\d,]*)\]")


def test_decode_program_builds_no_stacked_lanes():
    """The compiled decode program holds no array of B·L·W·KH·dh elements:
    no step gathers every row's whole table for every layer at once, or
    restacks the layers' lanes. Arrays of the pool's own shape are left
    out (the step copies the pool it does not own)."""
    cfg = get_smoke_config("llama3_2_3b").replace(dtype="float32",
                                                  vocab_size=2048)
    b, w = 32, 256
    L, kh, dh = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    be = _programs(cfg, None, max_batch=b, cache_len=w)
    pool = (64 + 1, L, BS, kh, dh)
    spec = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda: tfm.init_params(jax.random.PRNGKey(0),
                                                    cfg))
    i32 = jnp.int32
    hlo = be._decode_paged.lower(
        params, spec(pool, jnp.float32), spec(pool, jnp.float32),
        spec((b, 1), i32), spec((b,), i32), spec((b, w // BS), i32),
        spec((b,), i32), jax.random.PRNGKey(0)).compile().as_text()

    stacked = b * L * w * kh * dh
    shapes = {tuple(int(d) for d in m.split(",") if d)
              for m in _HLO_ARRAY.findall(hlo)}
    assert (b, 1, w, kh, dh) in shapes or (b, w, kh, dh) in shapes
    big = [s for s in shapes
           if s != pool and int(np.prod(s, dtype=np.int64)) >= stacked]
    assert not big, f"arrays of >= B*L*W*KH*dh elements: {big}"
