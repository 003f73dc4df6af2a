"""Real JAX serving backend: continuous batching over a slot-resident KV cache.

This is the integration the paper performs in vLLM, rebuilt TPU-idiomatically
(DESIGN.md §4) on top of the shared :class:`~repro.serving.core.ServingCore`
step loop: a fixed-capacity running batch of ``max_batch`` slots with static
shapes. The scheduler (and therefore PARS itself) is byte-identical to the
simulator path — only the backend and the clock differ.

Admission is **batched and prompt-length-bucketed**: the K requests admitted
in a cycle are padded to a small set of power-of-two token buckets and each
bucket runs as *one* jitted ``forward_seq`` (batch dimension also padded to a
power of two, so the set of compiled shapes is bounded) instead of K
sequential per-request dispatches. Decode gathers only the *active* slots
into a power-of-two-sized compact batch — idle lanes are never computed —
runs one jitted step, and scatters back. Padding lanes replay an active lane
with the same per-slot RNG key, so duplicate scatter writes are idempotent.

**Chunked prefill** (``prefill_chunk_tokens`` on :class:`Engine` /
:func:`serve`): the core plans mixed steps and this backend executes each
planned ``(req, start, end)`` chunk. A request's *first* chunk reuses the
bucketed ``_prefill_bucket`` + ``_place`` pair (a chunk starting at offset 0
is just a short prefill); *continuation* chunks run ``_extend_chunk``, which
writes the chunk's K/V into the request's cache lane at its current offset
and attends the chunk's queries over the already-resident prefix — exact
continuation, so chunked and unchunked serving produce identical greedy
outputs. A slot only joins the decode batch once its prompt is fully
resident (``core.decode_ready``). Chunked prefill requires an
attention-family model (DENSE/MoE/VLM) and an append-buffer cache
(``prompt_len <= cache_len``, no sliding window); recurrent families carry
cross-chunk state that ``forward_seq`` does not externalize.

**Paged KV** (``paged`` on :class:`Engine` / :func:`serve`; auto-on for
attention-family, non-enc-dec, non-sliding-window models): KV lives in one
global block pool ``(total_blocks + 1, L, block_size, KH, dh)`` per K and V
instead of per-slot lanes, indexed by each request's allocator block table
(plus a trailing *null* block that absorbs padded-table writes and whose
reads are always masked). Continuation chunks gather a request's table
into a contiguous lane; decode gathers each layer's lane from the pool inside
the layer loop. Both run the same forward/decode math as contiguous mode —
rows past the lane ``pos`` are masked to an exact constant, so outputs are
bit-identical, not approximately equal (``tests/test_paged_decode.py``) —
and write back only what the step wrote: the touched blocks of a chunk, one
row per layer of a decode. See docs/architecture.md
§"Paged KV" for the table lifecycle and the incremental
(``kv_reservation="incremental"``) grow-or-preempt contract.

**Prefix caching** (``prefix_caching=True`` on :class:`Engine` /
:func:`serve`): the core's allocator refcounts content-named KV blocks. In
paged mode a hit is **zero-copy**: the allocator aliased the committed
prefix blocks into the new request's table at reservation time and the pool
rows are the cache, so the backend just claims a slot and resumes prefill at
the cached offset (``prefix_tokens_copied`` stays 0). In contiguous mode
(``paged=False``) the backend keeps the historical hash-keyed **fragment
store**: per-block K/V slices are copied out of a donor lane at prompt
completion, and a hit concatenates the chain's fragments into the new lane
at ``[0, cached)`` before running ``_extend_chunk`` on the non-shared
suffix. Because attention at position i depends only on tokens ``<= i``,
the donor's prefix KV is bit-identical to what the recipient would have
computed itself — greedy outputs with caching on equal caching off
token-for-token (asserted in ``tests/test_prefix_caching.py``). The store
shrinks in lockstep with the allocator's LRU: an eviction listener drops the
fragment the moment accounting reclaims its block.

Prompt handling: prompts are hash-tokenized into their bucket. Completion
length follows the request's ground-truth ``true_length`` (the forced-length
protocol, DESIGN.md §3) — the engine generates real tokens, but *when* a
request finishes is the workload's ground truth, exactly as in the paper's
trace-driven evaluation.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import DENSE, MOE, VLM, ModelConfig
from repro.core.predictor.tokenizer import HashTokenizer
from repro.core.scheduler.request import Request
from repro.core.scheduler.scheduler import Scheduler
from repro.models import transformer as tfm
from repro.serving.config import ServingConfig, resolve_config
from repro.serving.core import PrefillChunk, ServingCore, WallClock
from repro.serving.kv_cache import (UNBOUNDED_BLOCKS, BlockAllocator,
                                    prefix_chunk_hashes)
from repro.serving.metrics import LatencyReport, RunCounters, report
from repro.serving.sampler import SamplerConfig, sample
from repro.tracing import span


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


class RealBackend:
    """Jitted prefill/decode over a slot-resident cache (ExecutionBackend)."""

    def __init__(self, cfg: ModelConfig, params, *, max_batch: int,
                 cache_len: int = 512, prompt_len: int = 32,
                 tokenizer: Optional[HashTokenizer] = None,
                 sampler: SamplerConfig = SamplerConfig(), seed: int = 0,
                 bucketed: bool = True, min_bucket: int = 8,
                 record_tokens: bool = False, paged: bool = False,
                 device: Optional[jax.Device] = None):
        self.cfg = cfg
        # every array this backend owns (params, KV, slot tokens, inputs) is
        # committed to one device, so N replicas in one process each run on
        # their own chip
        self.device = device or jax.devices()[0]
        self.params = jax.device_put(params, self.device)
        self.max_batch = max_batch
        self.cache_len = cache_len
        self.prompt_len = prompt_len
        self.bucketed = bucketed
        self.min_bucket = min(min_bucket, prompt_len)
        self.record_tokens = record_tokens
        self.paged = paged
        self.tok = tokenizer or HashTokenizer(
            vocab_size=min(cfg.vocab_size, 2048), max_len=prompt_len)
        self._key = self._put(jax.random.PRNGKey(seed))
        self.core: Optional[ServingCore] = None

        # --- slot state ------------------------------------------------------
        self.slot_req: List[Optional[Request]] = [None] * max_batch
        self._slot_of: Dict[int, int] = {}
        self._ids: Dict[int, List[int]] = {}    # req_id -> encoded prompt ids
        self.slot_tokens = self._put(np.zeros((max_batch, 1), np.int32))
        # one slot's cache shapes: contiguous mode allocates max_batch lanes
        # of it; paged mode only reads the K/V shape and dtype for its pool
        self._row_cache = jax.eval_shape(
            lambda: tfm.init_cache(cfg, 1, cache_len))
        self.cache = None if paged else jax.tree.map(
            lambda l: jnp.zeros((max_batch,) + l.shape, l.dtype,
                                device=self.device), self._row_cache)

        # --- prefix-cache fragment store (contiguous mode only) --------------
        # chunk-chain hash -> {"k": (L, block, kvH, D), "v": ...} device K/V of
        # one content-named block, copied out of a donor lane at prompt
        # completion; dropped via the allocator's eviction listener. Paged
        # mode has no store: a hit aliases pool blocks into the new table.
        self._prefix_store: Dict[int, dict] = {}

        # --- paged KV pool (built at attach: sized by the core's allocator) --
        # pools are (total_blocks + 1, L, block_size, KH, dh); the extra
        # trailing block is the *null* block — table padding that absorbs
        # out-of-reservation writes and whose reads are always masked
        self.k_pool = None
        self.v_pool = None
        self._null_block: Optional[int] = None
        self._lane_blocks: Optional[int] = None    # cache_len // block_size
        # req_id -> device-equivalent lane ``pos`` (tokens resident): set to
        # the prefill target at prompt completion, +1 per decode step —
        # mirrors the contiguous cache's per-slot ``pos`` leaf exactly,
        # including recompute re-admissions (where tokens_done is preserved
        # but the lane restarts at the target)
        self._pos: Dict[int, int] = {}

        # --- instrumentation -------------------------------------------------
        self.prefill_dispatches = 0   # jitted first-chunk forward_seq launches
        self.extend_dispatches = 0    # jitted continuation-chunk launches
        self.prefill_requests = 0     # requests whose prefill completed
        self.prefix_installs = 0      # lanes seeded from the fragment store
        self.prefix_tokens_copied = 0  # KV tokens installed instead of computed
        self.prefill_tokens = 0       # positions dispatched to prefill, lanes
        self.prefill_pad_tokens = 0   # × length; of them bucket or lane pads
        self.decode_rows = 0          # rows dispatched to the decode program
        self.decode_rows_padded = 0   # of them power-of-two padding

        # --- jitted programs -------------------------------------------------
        sampler_cfg = sampler
        self._sampler_cfg = sampler

        @jax.jit
        def _prefill_bucket(params, tokens, slot_ids, key):
            """First-chunk prefill for one token bucket.

            ``tokens`` is (B, bucket_len) int32 — the admitted prompts of one
            power-of-two length bucket, zero-padded on the right (token id 0
            acts as the pad token) and with padding *lanes* replaying lane 0.
            Runs one full-sequence forward with ``build_cache=True``, so the
            returned cache pytree holds every layer's K/V for positions
            [0, bucket_len), already padded out to ``cache_len`` rows by
            ``prefill_cache`` and carrying ``pos = bucket_len``.

            Also samples each lane's next token from ``logits[:, -1]`` — the
            request's first output token *if* this bucket covers its whole
            (padded) prompt; for a partial first chunk the sample is discarded
            by the caller. Per-slot keys (``fold_in``) make padding lanes that
            replay lane 0 sample the same token, keeping duplicate scatter
            writes idempotent.

            Returns ``(next_token (B,), cache)`` where cache leaves are
            (L, B, cache_len, ...) plus the ``pos`` scalar."""
            with jax.named_scope("model"):
                logits, cache, _ = tfm.forward_seq(
                    params, cfg, tokens, build_cache=True,
                    cache_len=cache_len, remat="none")
            keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(slot_ids)
            with jax.named_scope("sample"):
                nxt = jax.vmap(lambda lg, k: sample(lg, k, sampler_cfg))(
                    logits[:, -1], keys)
            return nxt, cache

        @jax.jit
        def _place(full_cache, bucket_cache, full_tokens, nxt, slot_ids):
            """Scatter a prefilled bucket's rows into their cache slots.

            ``full_cache`` leaves are (max_batch, L, 1, cache_len, ...) — one
            fixed lane per slot; ``bucket_cache`` leaves arrive from
            ``_prefill_bucket`` as (L, B, cache_len, ...) (scan-stacked, batch
            second). ``put`` transposes each bucket leaf to slot-major and
            writes whole lanes at ``slot_ids``; the scalar ``pos`` leaf
            broadcasts to every written slot, recording how many prompt
            tokens are resident (the chunk offset that ``_extend_chunk`` and
            decode continue from). ``nxt`` lands in ``full_tokens`` as each
            slot's pending decode input. Duplicate ``slot_ids`` (padding
            lanes) write identical values, so the scatter is idempotent."""
            def put(full, new):
                if new.ndim == 0:          # cache position: scalar per slot
                    return full.at[slot_ids].set(new)
                # (L, B, ...) bucket leaf → (B, L, 1, ...) slot rows
                return full.at[slot_ids].set(
                    jnp.expand_dims(jnp.moveaxis(new, 1, 0), 2))
            new_cache = jax.tree.map(put, full_cache, bucket_cache)
            return new_cache, full_tokens.at[slot_ids].set(nxt[:, None])

        @jax.jit
        def _extend_chunk(params, full_cache, full_tokens, tokens, slot_ids,
                          commit, key):
            """Continuation-chunk prefill at each slot's current offset.

            ``tokens`` is (B, chunk_len) int32 — the *next* chunk_len prompt
            tokens of B partially prefilled requests (padding lanes replay
            lane 0). Gathers those slots' cache rows, runs
            ``tfm.forward_chunk`` per row under ``vmap`` — each row carries
            its own ``pos`` leaf, so requests at *different* prefill offsets
            batch together; the chunk's K/V are written into the lane at
            [pos, pos+chunk_len) and its queries attend over the resident
            prefix, making the continuation exact — and scatters the
            extended rows back.

            Samples each lane's next token from the chunk's last position
            and commits it into ``full_tokens`` only where ``commit`` is set
            — the lanes whose prompt this chunk completes (mid-prompt
            samples are meaningless and must not clobber a pending decode
            token). Duplicate padding lanes carry lane 0's commit flag, so
            the scatter stays idempotent. Returns
            ``(new_full_tokens, new_full_cache)``."""
            sub = jax.tree.map(lambda l: l[slot_ids], full_cache)
            keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(slot_ids)

            def one(cache_row, toks, k):
                logits, new_row = tfm.forward_chunk(params, cfg, toks[None],
                                                    cache_row)
                return sample(logits[0, -1], k, sampler_cfg), new_row

            nxt, new_sub = jax.vmap(one)(sub, tokens, keys)
            new_cache = jax.tree.map(
                lambda full, s: full.at[slot_ids].set(s), full_cache, new_sub)
            kept = jnp.where(commit[:, None], nxt[:, None],
                             full_tokens[slot_ids])
            return full_tokens.at[slot_ids].set(kept), new_cache

        @jax.jit
        def _decode_active(params, cache, tokens, idx, key):
            """One decode iteration over the *active* slots only.

            ``idx`` (B,) lists the decode-ready slots, padded to a power of
            two with duplicates of ``idx[0]`` so the compiled-shape set stays
            bounded. Gathers those slots' cache rows and pending tokens, runs
            one ``tfm.decode_step`` per row under ``vmap`` (each row advances
            at its own ``pos``), samples the next token with per-slot folded
            keys, and scatters rows and tokens back. Because duplicate lanes
            compute identical values, the duplicate scatter writes are
            idempotent. Idle and mid-prefill slots are never touched —
            half-prefilled requests stay out of the decode batch entirely."""
            sub_cache = jax.tree.map(lambda l: l[idx], cache)
            sub_tokens = tokens[idx]
            keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)

            def one(cache_row, token_row, k):
                logits, new_row = tfm.decode_step(params, cfg, cache_row,
                                                  token_row[None])
                return sample(logits[0], k, sampler_cfg), new_row

            nxt, new_sub = jax.vmap(one)(sub_cache, sub_tokens, keys)
            new_cache = jax.tree.map(lambda full, sub: full.at[idx].set(sub),
                                     cache, new_sub)
            return tokens.at[idx].set(nxt[:, None]), new_cache

        self._prefill_bucket = _prefill_bucket
        self._place = _place
        self._extend_chunk = _extend_chunk
        self._decode_active = _decode_active

    # -------------------------------------------------------------- protocol
    def attach(self, core: ServingCore) -> None:
        self.core = core
        if core.prefill_chunk_tokens is not None or core.prefix_caching:
            # both features run _extend_chunk at non-zero offsets (a cache
            # hit resumes prefill mid-prompt even with chunking off), so
            # both need exact attention-family continuation
            if self.cfg.family not in (DENSE, MOE, VLM) or self.cfg.is_encdec:
                raise ValueError(
                    f"chunked prefill / prefix caching needs an "
                    f"attention-family model (got {self.cfg.family}): "
                    f"recurrent families carry cross-chunk state "
                    f"forward_seq does not externalize")
            if self.cfg.sliding_window or self.prompt_len > self.cache_len:
                raise ValueError(
                    "chunked prefill / prefix caching needs an append-buffer "
                    "cache covering the whole prompt (prompt_len <= "
                    "cache_len, no sliding window): continuation chunks "
                    "write at absolute offsets")
        if (core.prefill_chunk_tokens is not None
                and core.prefill_chunk_tokens > self.cache_len):
            raise ValueError(
                f"prefill_chunk_tokens={core.prefill_chunk_tokens} "
                f"exceeds cache_len={self.cache_len}: a continuation "
                f"chunk must fit the cache lane it extends")
        if core.prefix_caching and not self.paged:
            # keep the device-side store in lockstep with the accounting:
            # when the allocator reclaims a cached block, its KV goes too
            # (paged mode needs no mirror — the pool block *is* the cache
            # entry, and the allocator's refcount/LRU governs it directly)
            core.allocator.add_evict_listener(
                lambda h: self._prefix_store.pop(h, None))
        if self.paged:
            if self.cfg.family not in (DENSE, MOE, VLM) or self.cfg.is_encdec:
                raise ValueError(
                    f"paged KV needs an attention-family model (got "
                    f"{self.cfg.family}): recurrent / cross-attention "
                    f"caches are not block-structured")
            if self.cfg.sliding_window:
                raise ValueError(
                    "paged KV uses full-length block tables; sliding-window "
                    "lanes are shorter than the position space they cover")
            alloc = core.allocator
            if alloc.total_blocks >= UNBOUNDED_BLOCKS:
                raise ValueError("paged KV needs a bounded allocator: the "
                                 "pool is materialized at total_blocks")
            if self.cache_len % alloc.block_size or \
                    self.cache_len < alloc.block_size:
                raise ValueError(
                    f"paged KV needs block_size | cache_len "
                    f"(got {alloc.block_size} and {self.cache_len})")
            self._build_paged(alloc)

    # ------------------------------------------------------------- paged pool
    def _build_paged(self, alloc: BlockAllocator) -> None:
        """Materialize the global KV pool and compile the paged programs.

        Layout: ``(total_blocks + 1, L, block_size, KH, dh)`` per pool —
        block-major so one table entry is one contiguous row. The serving
        truth lives here; per dispatch a continuation chunk gathers a
        request's table into a contiguous ``(L, 1, cache_len, KH, dh)``
        lane and decode gathers one ``(1, cache_len, KH, dh)`` lane per
        layer; both run the *same* ``forward_chunk`` / ``layer_step`` math
        as contiguous mode (rows at positions >= pos are masked to an exact
        constant, so gathered-garbage lanes produce bit-identical outputs),
        and write only what the step wrote back into the pool."""
        row = self._row_cache["k"]                # (L, 1, W, KH, dh)
        L, _, W, KH, dh = row.shape
        bs = alloc.block_size
        n = alloc.total_blocks
        self._null_block = n
        self._lane_blocks = W // bs
        self.k_pool, self.v_pool = (
            jnp.zeros((n + 1, L, bs, KH, dh), row.dtype, device=self.device)
            for _ in range(2))
        self._paged_programs(bs)

    def _paged_programs(self, bs: int) -> None:
        """Define the jitted paged programs for block size ``bs``. They need
        only shapes, so a compile check can lower them without a pool."""
        cfg, sampler_cfg = self.cfg, self._sampler_cfg
        L, _, W, KH, dh = self._row_cache["k"].shape
        mb = W // bs

        def lane(pool, table):
            """Gather one table into a contiguous cache lane
            (max_blocks,) → (L, 1, W, KH, dh)."""
            x = pool[table]                       # (mb, L, bs, KH, dh)
            return jnp.moveaxis(x, 1, 0).reshape(L, 1, W, KH, dh)

        @jax.jit
        def _place_paged(k_pool, v_pool, bucket_k, bucket_v, full_tokens,
                         nxt, tables, slot_ids):
            """Scatter a prefilled bucket's leading blocks into the pool.

            ``bucket_k/v``: (L, B, W, KH, dh) from ``_prefill_bucket``;
            ``tables``: (B, nb) physical destination of each sequence's
            first nb = ceil(bucket_len / bs) blocks (padding lanes replay
            lane 0, so duplicate writes are idempotent; null entries absorb
            unreserved rows)."""
            nb = tables.shape[1]

            def to_blocks(x):                     # → (B, nb, L, bs, KH, dh)
                xb = x[:, :, :nb * bs].reshape(L, x.shape[1], nb, bs, KH, dh)
                return jnp.moveaxis(xb, (0, 1, 2), (2, 0, 1))

            with jax.named_scope("pool_write"):
                k_pool = k_pool.at[tables].set(to_blocks(bucket_k))
                v_pool = v_pool.at[tables].set(to_blocks(bucket_v))
            return k_pool, v_pool, full_tokens.at[slot_ids].set(nxt[:, None])

        @jax.jit
        def _extend_chunk_paged(params, k_pool, v_pool, full_tokens, tokens,
                                slot_ids, tables, starts, commit, key):
            """Continuation chunk over gathered lanes (paged twin of
            ``_extend_chunk``). Writes land at [start, start+C) in lane
            space; the touched blocks — at most ceil(C/bs)+1 of them — are
            sliced back out of the updated lane and scattered to their pool
            homes. Slice start and destination indices clamp identically,
            so a clamped window only re-writes unchanged blocks with their
            own content (bitwise no-op, shared-prefix safe)."""
            c = tokens.shape[1]
            nb_w = min(mb, -(-c // bs) + 1)
            keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(slot_ids)

            def one(table, toks, start, k):
                with jax.named_scope("lane_gather"):
                    row = {"k": lane(k_pool, table),
                           "v": lane(v_pool, table), "pos": start}
                with jax.named_scope("model"):
                    logits, new_row = tfm.forward_chunk(params, cfg,
                                                        toks[None], row)
                jc = jnp.clip(start // bs, 0, mb - nb_w)
                dest = jax.lax.dynamic_slice(table, (jc,), (nb_w,))
                dk = jax.lax.dynamic_slice(new_row["k"], (0, 0, jc * bs, 0, 0),
                                           (L, 1, nb_w * bs, KH, dh))
                dv = jax.lax.dynamic_slice(new_row["v"], (0, 0, jc * bs, 0, 0),
                                           (L, 1, nb_w * bs, KH, dh))
                with jax.named_scope("sample"):
                    nxt = sample(logits[0, -1], k, sampler_cfg)
                return nxt, dest, dk, dv

            nxt, dest, dk, dv = jax.vmap(one)(tables, tokens, starts, keys)

            def to_blocks(x):                     # → (B, nb_w, L, bs, KH, dh)
                xb = x[:, :, 0].reshape(x.shape[0], L, nb_w, bs, KH, dh)
                return jnp.moveaxis(xb, 2, 1)

            with jax.named_scope("pool_write"):
                k_pool = k_pool.at[dest].set(to_blocks(dk))
                v_pool = v_pool.at[dest].set(to_blocks(dv))
            kept = jnp.where(commit[:, None], nxt[:, None],
                             full_tokens[slot_ids])
            return full_tokens.at[slot_ids].set(kept), k_pool, v_pool

        @jax.jit
        def _decode_paged(params, k_pool, v_pool, full_tokens, idx, tables,
                          poss, key):
            """One decode iteration straight from the pool (paged twin of
            ``_decode_active``): each layer gathers its lane from the pool
            inside ``decode_step_paged``. ``poss`` is the host-tracked lane
            position per active slot; the one KV row per layer the step
            writes lands at row ``pos % bs`` of block
            ``table[(pos % W) // bs]`` — a wrap (pos >= W, caching off)
            overwrites the sequence's own oldest block, which is exactly
            the contiguous ring semantics. Padding rows replay a real row
            and null tables write the null block, so duplicate writes carry
            equal values."""
            sub_tokens = full_tokens[idx]
            keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(idx)

            def one(table, token_row, pos, k):
                with jax.named_scope("model"):
                    logits, k_rows, v_rows = tfm.decode_step_paged(
                        params, cfg, k_pool, v_pool, table, pos, token_row)
                with jax.named_scope("sample"):
                    nxt = sample(logits, k, sampler_cfg)
                return nxt, table[(pos % W) // bs], k_rows, v_rows

            nxt, dest, k_rows, v_rows = jax.vmap(one)(tables, sub_tokens, poss,
                                                      keys)
            row = poss % bs                       # bs divides W
            with jax.named_scope("pool_write"):
                k_pool = k_pool.at[dest, :, row].set(k_rows)
                v_pool = v_pool.at[dest, :, row].set(v_rows)
            return full_tokens.at[idx].set(nxt[:, None]), k_pool, v_pool

        self._place_paged = _place_paged
        self._extend_chunk_paged = _extend_chunk_paged
        self._decode_paged = _decode_paged

    def _table(self, req: Request, n: int) -> List[int]:
        """First ``n`` entries of the request's block table, null-padded —
        the per-dispatch physical index row. Unreserved requests (direct
        backend calls in unit tests) get an all-null table: their KV lands
        in the trash block and reads of it are masked."""
        tbl = (self.core.allocator.block_table(req.req_id)[:n]
               if self.core is not None else [])
        return tbl + [self._null_block] * (n - len(tbl))

    def kv_demand(self, req: Request) -> int:
        return self.prompt_len + min(req.true_length, self.cache_len)

    def prefix_tokens(self, req: Request) -> List[int]:
        """Prefix-sharing stream = the encoded *real* prompt ids (bucket
        padding is excluded: pad KV depends on where padding starts, so only
        whole blocks of real tokens are content-addressable)."""
        return self._prompt_ids(req)

    def prefill_total(self, req: Request) -> int:
        """Prompt tokens this engine actually prefills for ``req``: its
        hash-tokenized prompt padded up to the power-of-two bucket (or to
        ``prompt_len`` when bucketing is off). Chunk planning, the
        decode-ready check, and the first-output-token position all use this
        padded length, so chunked runs process the exact token stream the
        unchunked bucket path does."""
        return self._bucket_len(len(self._prompt_ids(req)))

    def _prompt_ids(self, req: Request) -> List[int]:
        """Encode (and cache) a prompt's token ids, truncated to
        ``prompt_len``. Cached for the request's residency so per-chunk
        slicing doesn't re-tokenize; dropped on ``release``."""
        ids = self._ids.get(req.req_id)
        if ids is None:
            ids = [t % self.cfg.vocab_size
                   for t in self.tok.encode(req.prompt)[:self.prompt_len]]
            self._ids[req.req_id] = ids
        return ids

    def _bucket_len(self, n_tokens: int) -> int:
        """Power-of-two token bucket for an ``n_tokens``-long prompt, clamped
        to [min_bucket, prompt_len]. Bounds the set of compiled prefill
        shapes; unbucketed mode pads everything to ``prompt_len``."""
        if not self.bucketed:
            return self.prompt_len
        return min(self.prompt_len, _next_pow2(max(n_tokens, self.min_bucket)))

    def bucket_lens(self) -> List[int]:
        if not self.bucketed:
            return [self.prompt_len]
        lens, b = [], self.min_bucket
        while b < self.prompt_len:
            lens.append(b)
            b *= 2
        return lens + [self.prompt_len]

    def warmup(self) -> float:
        """Pre-compile the (bucket_len × batch-size) shape grid, vLLM-style,
        so steady-state admission never pays jit. When the core is chunking,
        also compiles the continuation program for every (chunk, batch)
        shape. Returns wall seconds."""
        t0 = time.perf_counter()
        key = self._put(jax.random.PRNGKey(0))
        sizes, b = [], 1
        while b < _next_pow2(self.max_batch):
            sizes.append(b)
            b *= 2
        sizes.append(_next_pow2(self.max_batch))
        chunk = self.core.prefill_chunk_tokens if self.core else None
        # with power-of-two buckets and a power-of-two chunk the planner
        # only emits continuation chunks of exactly the budget length
        # (partial takes are head-of-line-only and bucket totals are
        # multiples of the chunk), so {chunk} is the whole extend grid; a
        # prefix-cache hit additionally admits at any block-multiple offset,
        # so its first suffix may be bucket − k·block_size long — warm the
        # *shortest* of those (bounded: long shared prefix + short unique
        # tail is the common hit shape, and an unbounded bucket×offset grid
        # would be O(prompt_len/block) compilations). Longer odd suffixes
        # lazily compile their length once, like odd chunk remainders.
        buckets = set(self.bucket_lens())
        ext_lens = {chunk} if chunk else set()
        if self.core is not None and self.core.prefix_caching:
            bs = self.core.allocator.block_size
            suffixes = sorted(bl - c for bl in buckets
                              for c in range(bs, bl, bs))
            ext_lens.update(suffixes[:8])
        bs = self.core.allocator.block_size if self.paged else 0
        for bl in sorted(buckets | ext_lens):
            for bsz in sizes:
                tokens = self._put(np.zeros((bsz, bl), np.int32))
                slots = self._put(np.zeros((bsz,), np.int32))
                no_commit = self._put(np.zeros((bsz,), bool))
                if bl in buckets:
                    nxt, cache = self._prefill_bucket(self.params, tokens,
                                                      slots, key)
                    if self.paged:
                        # null-block tables: the warm dispatches scribble on
                        # the trash block only
                        nb = -(-bl // bs)
                        out = self._place_paged(
                            self.k_pool, self.v_pool, cache["k"], cache["v"],
                            self.slot_tokens, nxt,
                            self._put(np.full((bsz, nb), self._null_block,
                                              np.int32)),
                            slots)
                    else:
                        out = self._place(self.cache, cache, self.slot_tokens,
                                          nxt, slots)
                    # one full-width program's outputs at a time: a cache and
                    # a pool copy each take a lane per slot of device memory
                    jax.block_until_ready(out)
                    del nxt, cache, out
                if bl in ext_lens:
                    if self.paged:
                        out = self._extend_chunk_paged(
                            self.params, self.k_pool, self.v_pool,
                            self.slot_tokens, tokens, slots,
                            self._put(np.full((bsz, self._lane_blocks),
                                              self._null_block, np.int32)),
                            self._put(np.zeros((bsz,), np.int32)),
                            no_commit, key)
                    else:
                        out = self._extend_chunk(
                            self.params, self.cache, self.slot_tokens, tokens,
                            slots, no_commit, key)
                    jax.block_until_ready(out)
                    del out
        if self.core is not None and self.core.prefix_caching and \
                not self.paged:
            # warm the prefix-install ops (fragment concat + lane scatters)
            # for every block-multiple offset. Scribbling on slot 0 is
            # harmless: a slot claim always rewrites [0, pos) before use and
            # attention never reads rows at positions >= pos — the same
            # masking that makes slot *reuse* safe without zeroing
            bs = self.core.allocator.block_size
            blk = self.cache["k"][0, :, 0, :bs]
            for c in range(bs, max(self.bucket_lens()), bs):
                k = jnp.concatenate([blk] * (c // bs), axis=1)
                self.cache["k"] = self.cache["k"].at[0, :, 0, :c].set(k)
                self.cache["v"] = self.cache["v"].at[0, :, 0, :c].set(k)
                self.cache["pos"] = self.cache["pos"].at[0].set(0)
        for bsz in sizes:
            if self.paged:
                out = self._decode_paged(
                    self.params, self.k_pool, self.v_pool, self.slot_tokens,
                    self._put(np.zeros((bsz,), np.int32)),
                    self._put(np.full((bsz, self._lane_blocks),
                                      self._null_block, np.int32)),
                    self._put(np.zeros((bsz,), np.int32)), key)
            else:
                out = self._decode_active(
                    self.params, self.cache, self.slot_tokens,
                    self._put(np.zeros((bsz,), np.int32)), key)
            jax.block_until_ready(out)
        return time.perf_counter() - t0

    def _put(self, x):
        """Commit a host array (or a PRNG key) to this backend's device."""
        return jax.device_put(x, self.device)

    def _now(self, fallback: float) -> float:
        return self.core.clock.now() if self.core is not None else fallback

    def _record(self, req: Request, token, now: float) -> None:
        if self.record_tokens:
            req.generated_tokens.append(int(token))
        if self.core is not None and self.core.record_token_times:
            req.token_times.append(now)

    def _tokens_snapshot(self) -> Optional[np.ndarray]:
        """One host copy of ``slot_tokens`` for ``_record``; None when
        neither recording flag is on (skip the device→host transfer)."""
        if self.record_tokens or (self.core is not None
                                  and self.core.record_token_times):
            return np.asarray(self.slot_tokens)
        return None

    # ------------------------------------------------------- prefix caching
    def _store_prefix(self, req: Request) -> None:
        """Copy the completed prompt's content-named per-block K/V slices
        out of its lane into the fragment store (skipping blocks already
        stored by an earlier identical prefix, and blocks the allocator
        isn't tracking — e.g. past the hit cap or with caching off)."""
        core = self.core
        if core is None or not core.prefix_caching:
            return
        bs = core.allocator.block_size
        slot = self._slot_of[req.req_id]
        for i, h in enumerate(prefix_chunk_hashes(self._prompt_ids(req), bs)):
            if h in self._prefix_store or not core.allocator.tracked(h):
                continue
            self._prefix_store[h] = {
                "k": self.cache["k"][slot, :, 0, i * bs:(i + 1) * bs],
                "v": self.cache["v"][slot, :, 0, i * bs:(i + 1) * bs]}

    def _install_prefix(self, slot: int, req: Request, n_tokens: int) -> None:
        """Seed a freshly claimed lane with a cached prefix: write the hit
        chain's fragments at positions [0, n_tokens) and set the lane's
        ``pos``, so prefill resumes at the cached offset. The blocks are
        refcount-pinned by this request's reservation, so every fragment is
        guaranteed present (commit-before-hit + the eviction listener)."""
        bs = self.core.allocator.block_size
        hashes = prefix_chunk_hashes(self._prompt_ids(req), bs)[:n_tokens // bs]
        frags = [self._prefix_store[h] for h in hashes]
        k = jnp.concatenate([f["k"] for f in frags], axis=1)
        v = jnp.concatenate([f["v"] for f in frags], axis=1)
        self.cache["k"] = self.cache["k"].at[slot, :, 0, :n_tokens].set(k)
        self.cache["v"] = self.cache["v"].at[slot, :, 0, :n_tokens].set(v)
        self.cache["pos"] = self.cache["pos"].at[slot].set(n_tokens)
        self.prefix_installs += 1
        self.prefix_tokens_copied += n_tokens

    def _count_prefill(self, positions: int, real: int) -> None:
        """One prefill dispatch of ``positions`` token positions (lanes ×
        length), ``real`` of them prompt tokens: the rest is padding."""
        self.prefill_tokens += positions
        self.prefill_pad_tokens += positions - real

    def prefill(self, chunks: Sequence[PrefillChunk], now: float) -> float:
        """Execute one step's planned prefill chunks (see ``ServingCore``).

        First chunks (``start == 0``) claim a free slot and run the bucketed
        ``_prefill_bucket``/``_place`` path, grouped by chunk length — with
        chunking off every chunk is a whole padded prompt and this *is* the
        historical one-dispatch-per-bucket admission. A prefix-cache hit's
        first chunk arrives with ``start > 0`` and no slot: it claims one,
        seeds it from the fragment store (``_install_prefix``), and then
        runs as a continuation. Continuation chunks run ``_extend_chunk``
        grouped by length; requests at different offsets share a dispatch
        since the offset is per-lane data. A request whose chunk reaches
        ``prefill_total`` gets its first output token committed
        (tokens_done/TTFT bookkeeping preserved across preemption
        re-admission, matching SimBackend's recompute semantics) and its
        prefix blocks' KV copied into the fragment store."""
        with span("engine.prefill"):
            return self._prefill(chunks, now)

    def _prefill(self, chunks: Sequence[PrefillChunk], now: float) -> float:
        if not chunks:
            return now
        first_groups: Dict[int, list] = {}
        ext_groups: Dict[int, list] = {}
        for req, start, end in chunks:
            if req.req_id not in self._slot_of:
                slot = self.slot_req.index(None)
                self.slot_req[slot] = req
                self._slot_of[req.req_id] = slot
                if self.paged and self.core is not None \
                        and self.core.prefix_caching \
                        and self.prefill_total(req) + req.true_length - 1 \
                        > self.cache_len:
                    raise ValueError(
                        f"paged KV with prefix caching cannot ring-wrap: "
                        f"request {req.req_id} needs "
                        f"{self.prefill_total(req) + req.true_length - 1} "
                        f"positions > cache_len={self.cache_len} (a wrap "
                        f"would overwrite potentially shared prefix blocks)")
                if start > 0:               # admission at a cached offset
                    if self.paged:
                        # zero-copy hit: the reservation already aliased the
                        # shared prefix blocks into this request's table, so
                        # the pool rows *are* its cache — no KV moves, the
                        # suffix chunk below just resumes at ``start``
                        self.prefix_installs += 1
                    else:
                        self._install_prefix(slot, req, start)
            if start == 0:
                first_groups.setdefault(end, []).append(req)
            else:
                ext_groups.setdefault(end - start, []).append((req, start, end))

        if self.bucketed:
            first_batches = sorted(first_groups.items())
        else:                          # sequential: one dispatch per request
            first_batches = [(ln, [r]) for ln, g in sorted(first_groups.items())
                             for r in g]
        for bucket_len, group in first_batches:
            b = _next_pow2(len(group))
            tokens = np.zeros((b, bucket_len), np.int32)
            slots = np.zeros((b,), np.int32)
            for j, req in enumerate(group):
                ids = self._prompt_ids(req)[:bucket_len]
                tokens[j, :len(ids)] = ids
                slots[j] = self._slot_of[req.req_id]
            tokens[len(group):] = tokens[0]     # padding lanes replay lane 0
            slots[len(group):] = slots[0]
            self._count_prefill(tokens.size, sum(
                min(len(self._prompt_ids(r)), bucket_len) for r in group))
            self._key, sub = jax.random.split(self._key)
            slots_j = self._put(slots)
            nxt, bucket_cache = self._prefill_bucket(
                self.params, self._put(tokens), slots_j, sub)
            if self.paged:
                bs = self.core.allocator.block_size
                nb = -(-bucket_len // bs)
                tables = np.full((b, nb), self._null_block, np.int32)
                for j, req in enumerate(group):
                    tables[j] = self._table(req, nb)
                tables[len(group):] = tables[0]
                self.k_pool, self.v_pool, self.slot_tokens = self._place_paged(
                    self.k_pool, self.v_pool, bucket_cache["k"],
                    bucket_cache["v"], self.slot_tokens, nxt,
                    self._put(tables), slots_j)
            else:
                self.cache, self.slot_tokens = self._place(
                    self.cache, bucket_cache, self.slot_tokens, nxt, slots_j)
            # the bucket's cache is a lane per slot: free it before the
            # next program allocates
            del nxt, bucket_cache
            self.prefill_dispatches += 1

        for chunk_len, group in sorted(ext_groups.items()):
            b = _next_pow2(len(group))
            tokens = np.zeros((b, chunk_len), np.int32)
            slots = np.zeros((b,), np.int32)
            starts = np.zeros((b,), np.int32)
            commit = np.zeros((b,), bool)
            for j, (req, start, end) in enumerate(group):
                ids = self._prompt_ids(req)[start:end]
                tokens[j, :len(ids)] = ids      # tail past len(ids) = pad 0s
                slots[j] = self._slot_of[req.req_id]
                starts[j] = start
                commit[j] = end >= self.prefill_total(req)
            tokens[len(group):] = tokens[0]
            slots[len(group):] = slots[0]
            starts[len(group):] = starts[0]
            self._count_prefill(tokens.size, sum(
                len(self._prompt_ids(r)[s:e]) for r, s, e in group))
            commit[len(group):] = commit[0]
            self._key, sub = jax.random.split(self._key)
            if self.paged:
                tables = np.full((b, self._lane_blocks), self._null_block,
                                 np.int32)
                for j, (req, _s, _e) in enumerate(group):
                    tables[j] = self._table(req, self._lane_blocks)
                tables[len(group):] = tables[0]
                self.slot_tokens, self.k_pool, self.v_pool = \
                    self._extend_chunk_paged(
                        self.params, self.k_pool, self.v_pool,
                        self.slot_tokens, self._put(tokens),
                        self._put(slots), self._put(tables),
                        self._put(starts), self._put(commit), sub)
            else:
                self.slot_tokens, self.cache = self._extend_chunk(
                    self.params, self.cache, self.slot_tokens,
                    self._put(tokens), self._put(slots),
                    self._put(commit), sub)
            self.extend_dispatches += 1

        with span("engine.prefill.sync"):
            jax.block_until_ready(self.slot_tokens)
        now = self._now(now)
        toks = self._tokens_snapshot()
        for req, _start, end in chunks:
            if end < self.prefill_total(req):
                continue                        # still mid-prompt
            self.prefill_requests += 1
            if self.paged:
                # the pool blocks *are* the citable KV (the core commits
                # their hashes); start the host mirror of the lane pos
                self._pos[req.req_id] = self.prefill_total(req)
            else:
                self._store_prefix(req)         # prompt KV is now citable
            # recompute semantics on re-admission after preemption: decode
            # progress and TTFT are preserved, matching SimBackend
            if req.tokens_done == 0:
                req.tokens_done = 1             # prefill emits token 1
                if toks is not None:
                    self._record(req, toks[self._slot_of[req.req_id], 0], now)
            if req.first_token_time is None:
                req.first_token_time = now
        return now

    def decode(self, now: float) -> float:
        """One decode token for every decode-ready slot, in one dispatch of
        the active rows padded to a power of two."""
        with span("engine.decode"):
            return self._decode(now)

    def _decode(self, now: float) -> float:
        ready = (self.core.decode_ready if self.core is not None
                 else lambda r: True)
        active = [i for i, r in enumerate(self.slot_req)
                  if r is not None and ready(r)]
        if not active:
            return now
        idx = np.asarray(
            active + [active[0]] * (_next_pow2(len(active)) - len(active)),
            np.int32)
        self.decode_rows += len(idx)
        self.decode_rows_padded += len(idx) - len(active)
        self._key, sub = jax.random.split(self._key)
        if self.paged:
            tables = np.full((len(idx), self._lane_blocks), self._null_block,
                             np.int32)
            poss = np.zeros((len(idx),), np.int32)
            for j, i in enumerate(idx):
                req = self.slot_req[i]
                tables[j] = self._table(req, self._lane_blocks)
                poss[j] = self._pos[req.req_id]
            self.slot_tokens, self.k_pool, self.v_pool = self._decode_paged(
                self.params, self.k_pool, self.v_pool, self.slot_tokens,
                self._put(idx), self._put(tables), self._put(poss), sub)
        else:
            self.slot_tokens, self.cache = self._decode_active(
                self.params, self.cache, self.slot_tokens, self._put(idx),
                sub)
        with span("engine.decode.sync"):
            jax.block_until_ready(self.slot_tokens)
        now = self._now(now)
        toks = self._tokens_snapshot()
        for i in active:
            self.slot_req[i].tokens_done += 1
            if self.paged:
                self._pos[self.slot_req[i].req_id] += 1
            if toks is not None:
                self._record(self.slot_req[i], toks[i, 0], now)
        return now

    def release(self, req: Request) -> None:
        self._ids.pop(req.req_id, None)
        self._pos.pop(req.req_id, None)
        slot = self._slot_of.pop(req.req_id, None)
        if slot is not None:
            self.slot_req[slot] = None


class Engine:
    """RealBackend + ServingCore wiring (the historical engine interface)."""

    def __init__(self, cfg: ModelConfig, params, scheduler: Scheduler, *,
                 cache_len: int = 512, prompt_len: int = 32,
                 tokenizer: Optional[HashTokenizer] = None,
                 allocator: Optional[BlockAllocator] = None,
                 sampler: SamplerConfig = SamplerConfig(), seed: int = 0,
                 bucketed: bool = True,
                 paged: Optional[bool] = None,
                 record_tokens: bool = False,
                 device: Optional[jax.Device] = None,
                 config: Optional[ServingConfig] = None,
                 **core_kw):
        if paged is None:
            # auto: block-structured KV exists exactly for attention-family
            # append caches; recurrent/enc-dec/sliding-window lanes keep the
            # historical contiguous path
            paged = (cfg.family in (DENSE, MOE, VLM) and not cfg.is_encdec
                     and not cfg.sliding_window)
        # core behaviour: config=ServingConfig(...) or loose keywords
        # (chunking, caching, reservation, re-ranking, deadlines, shedding)
        # — a blessed translation, no deprecation warning
        config = resolve_config(config, core_kw)
        s = scheduler.max_batch
        self.scheduler = scheduler
        self.backend = RealBackend(
            cfg, params, max_batch=s, cache_len=cache_len,
            prompt_len=prompt_len, tokenizer=tokenizer, sampler=sampler,
            seed=seed, bucketed=bucketed, record_tokens=record_tokens,
            paged=paged, device=device)
        self.allocator = allocator or BlockAllocator(
            total_blocks=s * (-(-cache_len // 16)), block_size=16)
        self.core = ServingCore(scheduler, self.backend,
                                allocator=self.allocator, config=config)

    # -------------------------------------------------------------------- api
    @property
    def finished(self) -> List[Request]:
        return self.core.finished

    def submit(self, requests: Sequence[Request]) -> None:
        self.core.submit(requests)

    def warmup(self) -> float:
        return self.backend.warmup()

    def run(self, *, time_scale: float = 1.0, log_every: float = 0.0,
            log_fn=print) -> List[Request]:
        """Serve everything submitted; returns finished requests.

        ``time_scale`` multiplies trace arrival times (replay a GPU-scale
        trace on CPU without idling)."""
        if time_scale != 1.0:
            for r in self.core._pending:
                r.arrival_time *= time_scale
        self.core.clock = WallClock()           # origin = serving start
        return self.core.run(log_every=log_every, log_fn=log_fn)


def serve(cfg: ModelConfig, params, requests: Sequence[Request], policy, *,
          max_batch: int = 8, cache_len: int = 256, prompt_len: int = 32,
          starvation_threshold: float = 120.0, time_scale: float = 1.0,
          log_every: float = 0.0, bucketed: bool = True,
          kv_blocks: Optional[int] = None,
          paged: Optional[bool] = None,
          config: Optional[ServingConfig] = None,
          **core_kw) -> LatencyReport:
    """Convenience wrapper: fresh engine + scheduler, serve, report. Core
    behaviour comes from ``config`` or loose keywords (chunking, caching,
    reservation mode, re-ranking, deadlines, shedding, …); dropped requests
    are counted in the report, never silently lost."""
    config = resolve_config(config, core_kw)
    sched = Scheduler(policy=policy, max_batch=max_batch,
                      starvation_threshold=starvation_threshold)
    allocator = BlockAllocator(kv_blocks, 16) if kv_blocks else None
    eng = Engine(cfg, params, sched, cache_len=cache_len,
                 prompt_len=prompt_len, allocator=allocator,
                 bucketed=bucketed, paged=paged, config=config)
    eng.submit(requests)
    finished = eng.run(time_scale=time_scale, log_every=log_every)
    dropped = eng.core.dropped
    assert len(finished) + len(dropped) == len(requests), \
        (len(finished), len(dropped), len(requests))
    return report(policy.name, finished,
                  counters=RunCounters(
                      reranks=(eng.core.rerank_count
                               if config.rerank_enabled else None),
                      dropped=tuple(dropped) if dropped else None))
