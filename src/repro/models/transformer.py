"""Generic multi-family transformer: dense / MoE / VLM / hybrid / SSM / enc-dec.

One parameter layout and three execution modes per architecture family:

* ``forward_seq``   — full-sequence forward (training and prefill; prefill
  additionally materializes the decode cache).
* ``decode_step``   — one-token step against the cache/state pytree.
* ``encode``        — whisper-style bidirectional encoder over stub frames.

Layers are *stacked*: every leaf in ``params["layers"]`` has a leading
``num_layers`` axis and the layer loop is a single ``jax.lax.scan`` — this
keeps HLO size independent of depth (80-layer configs lower in seconds) and
gives remat a natural grain (one scan body).

All activations are tagged with logical sharding axes (see
repro/sharding/annotate.py); on CPU smoke tests the tags are no-ops.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ModelConfig, DENSE, MOE, SSM, HYBRID, VLM,
                                AUDIO)
from repro.models import rwkv6 as rwkv
from repro.models import ssm as ssd
from repro.models.attention import (attention, decode_attention, prefill_cache,
                                    update_cache)
from repro.models.common import (activate, apply_norm, apply_mrope, apply_rope,
                                 dense_init, embed_init, gated, init_norm,
                                 positions_for)
from repro.models.moe import apply_moe, init_moe
from repro.sharding.annotate import with_sharding

PyTree = Any


# ===========================================================================
# Parameter initialization
# ===========================================================================
def _init_attn(key, cfg: ModelConfig, dtype) -> dict:
    d, h, kh, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, h * dh), dtype=dtype),
        "wk": dense_init(ks[1], (d, kh * dh), dtype=dtype),
        "wv": dense_init(ks[2], (d, kh * dh), dtype=dtype),
        "wo": dense_init(ks[3], (h * dh, d), in_axis_size=h * dh, dtype=dtype),
    }
    if cfg.use_bias:
        p.update(bq=jnp.zeros((h * dh,), dtype), bk=jnp.zeros((kh * dh,), dtype),
                 bv=jnp.zeros((kh * dh,), dtype), bo=jnp.zeros((d,), dtype))
    return p


def _init_mlp(key, cfg: ModelConfig, dtype) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {
        "w_up": dense_init(ks[0], (d, f), dtype=dtype),
        "w_down": dense_init(ks[1], (f, d), in_axis_size=f, dtype=dtype),
    }
    if gated(cfg.activation):
        p["w_gate"] = dense_init(ks[2], (d, f), dtype=dtype)
    if cfg.use_bias:
        p.update(b_up=jnp.zeros((f,), dtype), b_down=jnp.zeros((d,), dtype))
    return p


def _init_layer(key, cfg: ModelConfig, dtype, *, encoder: bool = False) -> dict:
    ks = jax.random.split(key, 8)
    p: dict = {"ln1": init_norm(cfg, dtype), "ln2": init_norm(cfg, dtype)}
    if cfg.family == SSM:
        p["tm"] = rwkv.init_time_mix(ks[0], cfg, dtype)
        p["cm"] = rwkv.init_channel_mix(ks[1], cfg, dtype)
        return p
    p["attn"] = _init_attn(ks[0], cfg, dtype)
    if cfg.family == HYBRID:
        p["ssm"] = ssd.init_ssm(ks[1], cfg, dtype)
    if not encoder and cfg.is_encdec:
        p["cross"] = _init_attn(ks[2], cfg, dtype)
        p["ln_cross"] = init_norm(cfg, dtype)
    if cfg.moe is not None and not encoder:
        p["moe"] = init_moe(ks[3], cfg.d_model, cfg.moe, cfg.activation, dtype)
        if cfg.d_ff:  # shared dense path (DeepSeek-style shared expert)
            p["mlp"] = _init_mlp(ks[4], cfg, dtype)
    elif cfg.d_ff:
        p["mlp"] = _init_mlp(ks[4], cfg, dtype)
    return p


def init_params(key, cfg: ModelConfig, param_dtype=None) -> PyTree:
    dtype = jnp.dtype(param_dtype or cfg.dtype)
    k_embed, k_layers, k_head, k_enc, k_pos = jax.random.split(key, 5)
    params: Dict[str, Any] = {
        "embed": embed_init(k_embed, (cfg.vocab_size, cfg.d_model), dtype),
        "final_norm": init_norm(cfg, dtype),
    }
    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    params["layers"] = jax.vmap(
        lambda k: _init_layer(k, cfg, dtype))(layer_keys)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (cfg.d_model, cfg.vocab_size),
                                       dtype=dtype)
    if cfg.pos_emb == "learned":
        params["pos_embed"] = embed_init(k_pos, (8192, cfg.d_model), dtype)
    if cfg.is_encdec:
        enc_keys = jax.random.split(k_enc, cfg.encoder_layers + 2)
        params["encoder"] = {
            "layers": jax.vmap(
                lambda k: _init_layer(k, cfg, dtype, encoder=True))(
                    enc_keys[:cfg.encoder_layers]),
            "pos_embed": embed_init(enc_keys[-2],
                                    (cfg.encoder_seq_len, cfg.d_model), dtype),
            "final_norm": init_norm(cfg, dtype),
        }
    return params


# ===========================================================================
# Attention block (seq + step)
# ===========================================================================
def _qkv(p, x, cfg: ModelConfig):
    b = x.shape[0]
    s = x.shape[1] if x.ndim == 3 else 1
    h, kh, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = x @ p["wq"] + (p["bq"] if "bq" in p else 0)
    k = x @ p["wk"] + (p["bk"] if "bk" in p else 0)
    v = x @ p["wv"] + (p["bv"] if "bv" in p else 0)
    return (q.reshape(b, s, h, dh), k.reshape(b, s, kh, dh),
            v.reshape(b, s, kh, dh))


def _rope_qk(q, k, cfg: ModelConfig, positions, mrope_pos):
    if cfg.pos_emb == "mrope":
        return (apply_mrope(q, mrope_pos, cfg.rope_theta),
                apply_mrope(k, mrope_pos, cfg.rope_theta))
    if cfg.pos_emb == "rope":
        return (apply_rope(q, positions, cfg.rope_theta),
                apply_rope(k, positions, cfg.rope_theta))
    return q, k


def attn_seq(p, x, cfg: ModelConfig, *, positions, mrope_pos=None,
             causal=True, attn_impl="chunked", kv_chunk=1024,
             kv_override=None):
    """Full-sequence attention. Returns (out, (k, v)) for cache building."""
    b, s, _ = x.shape
    q, k, v = _qkv(p, x, cfg)
    if kv_override is not None:          # cross-attention: K/V from encoder
        k, v = kv_override
        pos_kv = positions_for(cfg, b, 0, k.shape[1])
    else:
        q, k = _rope_qk(q, k, cfg, positions, mrope_pos)
        pos_kv = positions
    q = with_sharding(q, ("batch", None, "heads", None))
    k = with_sharding(k, ("batch", None, "kv_heads", None))
    out = attention(q, k, v, positions, pos_kv, causal=causal,
                    window=cfg.sliding_window if causal else None,
                    impl=attn_impl, kv_chunk=kv_chunk)
    out = out.reshape(b, s, -1) @ p["wo"] + (p["bo"] if "bo" in p else 0)
    return out, (k, v)


def attn_step(p, x, cfg: ModelConfig, *, cache_k, cache_v, pos,
              mrope_pos=None, cross=False):
    """One-token attention. x: (B,1,d); caches: (B,W,KH,dh); pos scalar."""
    b = x.shape[0]
    q, k, v = _qkv(p, x, cfg)
    if cross:
        # cross-attention: cache holds the (fixed) encoder K/V; no update
        pos_kv = jnp.broadcast_to(
            jnp.arange(cache_k.shape[1], dtype=jnp.int32)[None],
            (b, cache_k.shape[1]))
        pos_q = jnp.full((b, 1), cache_k.shape[1], jnp.int32)  # attend to all
        out = attention(q, cache_k, cache_v, pos_q, pos_kv, causal=False)
        out = out.reshape(b, 1, -1) @ p["wo"] + (p["bo"] if "bo" in p else 0)
        return out, cache_k, cache_v
    positions = jnp.broadcast_to(jnp.asarray(pos, jnp.int32)[None, None], (b, 1))
    mp = (jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (3, b, 1))
          if cfg.pos_emb == "mrope" else None)
    q, k = _rope_qk(q, k, cfg, positions, mp)
    cache_k = update_cache(cache_k, k[:, 0], pos)
    cache_v = update_cache(cache_v, v[:, 0], pos)
    out = decode_attention(q[:, 0], cache_k, cache_v, pos,
                           window=cfg.sliding_window)
    out = out.reshape(b, 1, -1) @ p["wo"] + (p["bo"] if "bo" in p else 0)
    return out, cache_k, cache_v


def _mlp(p, x, cfg: ModelConfig):
    up = x @ p["w_up"] + (p["b_up"] if "b_up" in p else 0)
    gate = x @ p["w_gate"] if "w_gate" in p else None
    h = activate(up, gate, cfg.activation)
    h = with_sharding(h, ("batch", None, "ff"))
    return h @ p["w_down"] + (p["b_down"] if "b_down" in p else 0)


def _ffn(lp, x, cfg: ModelConfig):
    """Dense MLP, MoE, or both (shared-expert). Returns (y, aux_losses)."""
    aux = {"load_balance_loss": 0.0, "router_z_loss": 0.0}
    y = 0.0
    if "moe" in lp:
        y_moe, aux = apply_moe(lp["moe"], x, cfg.moe, cfg.activation)
        y = y + y_moe
    if "mlp" in lp:
        y = y + _mlp(lp["mlp"], x, cfg)
    return y, aux


# ===========================================================================
# Layer bodies (per family) — sequence mode
# ===========================================================================
def layer_seq(lp, x, cfg: ModelConfig, *, positions, mrope_pos, enc_out,
              build_cache, cache_len, attn_impl, kv_chunk, chunk_size):
    """One decoder layer, full-sequence. Returns (x, cache_slices, aux)."""
    cache: Dict[str, jax.Array] = {}
    xn = apply_norm(lp["ln1"], x, cfg.norm)
    if cfg.family == SSM:
        b = x.shape[0]
        prev = jnp.zeros((b, cfg.d_model), x.dtype)
        out, last_tm, state = rwkv.time_mix(lp["tm"], xn, prev, cfg,
                                            chunk_size=chunk_size)
        x = x + out
        xn2 = apply_norm(lp["ln2"], x, cfg.norm)
        out2, last_cm = rwkv.channel_mix(lp["cm"], xn2, prev)
        x = x + out2
        if build_cache:
            cache = {"state": state, "tm_prev": last_tm, "cm_prev": last_cm}
        return x, cache, {}

    attn_out, (k, v) = attn_seq(lp["attn"], xn, cfg, positions=positions,
                                mrope_pos=mrope_pos, attn_impl=attn_impl,
                                kv_chunk=kv_chunk)
    if cfg.family == HYBRID:
        ssm_out, conv_tail, state = ssd.apply_ssm(lp["ssm"], xn, cfg)
        attn_out = 0.5 * (attn_out + ssm_out)
        if build_cache:
            cache.update(conv_tail=conv_tail, ssm_state=state)
    x = x + attn_out
    if build_cache and not cfg.attention_free:
        ck, cv = prefill_cache(k, v, cache_len)
        cache.update(k=ck, v=cv)

    if enc_out is not None:                      # whisper cross-attention
        xc = apply_norm(lp["ln_cross"], x, cfg.norm)
        _, ek, ev = _qkv(lp["cross"], enc_out, cfg)  # K/V from encoder
        # queries from decoder: reuse attn_seq with kv_override
        cross_out, _ = attn_seq(lp["cross"], xc, cfg, positions=positions,
                                kv_override=(ek, ev), causal=False,
                                attn_impl=attn_impl, kv_chunk=kv_chunk)
        x = x + cross_out
        if build_cache:
            cache.update(cross_k=ek, cross_v=ev)

    xn2 = apply_norm(lp["ln2"], x, cfg.norm)
    ffn_out, aux = _ffn(lp, xn2, cfg)
    x = x + ffn_out
    return x, cache, aux


def layer_step(lp, x, cfg: ModelConfig, cache_l: Dict[str, jax.Array], pos):
    """One decoder layer, one token. x: (B,1,d)."""
    new_cache = dict(cache_l)
    xn = apply_norm(lp["ln1"], x, cfg.norm)
    if cfg.family == SSM:
        out, last_tm, state = rwkv.time_mix_step(
            lp["tm"], xn[:, 0], cache_l["tm_prev"], cache_l["state"], cfg)
        x = x + out[:, None]
        xn2 = apply_norm(lp["ln2"], x, cfg.norm)
        out2, last_cm = rwkv.channel_mix_step(lp["cm"], xn2[:, 0],
                                              cache_l["cm_prev"])
        x = x + out2[:, None]
        new_cache.update(state=state, tm_prev=last_tm, cm_prev=last_cm)
        return x, new_cache, {}

    attn_out, ck, cv = attn_step(lp["attn"], xn, cfg, cache_k=cache_l["k"],
                                 cache_v=cache_l["v"], pos=pos)
    new_cache.update(k=ck, v=cv)
    if cfg.family == HYBRID:
        ssm_out, conv_tail, state = ssd.ssm_step(
            lp["ssm"], xn[:, 0], cfg, cache_l["conv_tail"], cache_l["ssm_state"])
        attn_out = 0.5 * (attn_out + ssm_out[:, None])
        new_cache.update(conv_tail=conv_tail, ssm_state=state)
    x = x + attn_out

    if "cross_k" in cache_l:
        xc = apply_norm(lp["ln_cross"], x, cfg.norm)
        cross_out, _, _ = attn_step(lp["cross"], xc, cfg,
                                    cache_k=cache_l["cross_k"],
                                    cache_v=cache_l["cross_v"], pos=pos,
                                    cross=True)
        x = x + cross_out

    xn2 = apply_norm(lp["ln2"], x, cfg.norm)
    ffn_out, aux = _ffn(lp, xn2, cfg)
    x = x + ffn_out
    return x, new_cache, aux


# ===========================================================================
# Whisper encoder
# ===========================================================================
def encode(params, cfg: ModelConfig, frames: jax.Array,
           attn_impl="chunked") -> jax.Array:
    """Bidirectional encoder over stub frame embeddings (B, F, d)."""
    enc = params["encoder"]
    b, f, _ = frames.shape
    x = frames + enc["pos_embed"][None, :f].astype(frames.dtype)
    positions = positions_for(cfg, b, 0, f)

    def body(x, lp):
        xn = apply_norm(lp["ln1"], x, cfg.norm)
        out, _ = attn_seq(lp["attn"], xn, cfg, positions=positions,
                          causal=False, attn_impl=attn_impl)
        x = x + out
        xn2 = apply_norm(lp["ln2"], x, cfg.norm)
        x = x + _mlp(lp["mlp"], xn2, cfg)
        return x, None

    x, _ = jax.lax.scan(body, x, enc["layers"])
    return apply_norm(enc["final_norm"], x, cfg.norm)


# ===========================================================================
# Top level: embed → layers (scan) → norm → logits
# ===========================================================================
def _embed(params, cfg: ModelConfig, tokens, *, start_pos=0,
           vision_embeds=None):
    x = params["embed"][tokens]                 # (B,S,d) gather
    if vision_embeds is not None:
        x = jnp.concatenate([vision_embeds.astype(x.dtype), x], axis=1)
    if cfg.pos_emb == "learned":
        s = x.shape[1]
        table = params["pos_embed"]
        # modular wrap: the assigned stress shapes (32k/500k decode) exceed
        # any learned-position model's table; wrapping keeps the program
        # well-defined (DESIGN.md §5 — whisper runs decode_32k as a stress
        # config, not a semantic claim)
        ids = jnp.mod(start_pos + jnp.arange(s, dtype=jnp.int32),
                      table.shape[0])
        x = x + table[ids][None].astype(x.dtype)
    return with_sharding(x, ("batch", None, None))


def _unembed(params, cfg: ModelConfig, x):
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x @ head
    return with_sharding(logits, ("batch", None, "vocab"))


def forward_seq(params, cfg: ModelConfig, tokens, *,
                vision_embeds=None, mrope_positions=None, frames=None,
                build_cache=False, cache_len=0,
                attn_impl="chunked", kv_chunk=1024, chunk_size=64,
                remat: str = "full") -> Tuple[jax.Array, Optional[PyTree], dict]:
    """Full-sequence forward.

    Returns (logits (B,S,V), cache-or-None, aux_losses). When
    ``build_cache`` (prefill), the cache pytree has stacked (L, ...) leaves
    plus a ``pos`` scalar.
    """
    x = _embed(params, cfg, tokens, vision_embeds=vision_embeds)
    b, s, _ = x.shape
    positions = positions_for(cfg, b, 0, s)
    mrope_pos = mrope_positions
    if cfg.pos_emb == "mrope" and mrope_pos is None:
        mrope_pos = jnp.broadcast_to(positions[None], (3, b, s))
    enc_out = encode(params, cfg, frames, attn_impl) if cfg.is_encdec else None

    def body(carry, lp):
        x, lb, zl = carry
        x, cache, aux = layer_seq(
            lp, x, cfg, positions=positions, mrope_pos=mrope_pos,
            enc_out=enc_out, build_cache=build_cache, cache_len=cache_len,
            attn_impl=attn_impl, kv_chunk=kv_chunk, chunk_size=chunk_size)
        lb = lb + aux.get("load_balance_loss", 0.0)
        zl = zl + aux.get("router_z_loss", 0.0)
        return (x, lb, zl), cache

    if remat == "full":
        body = jax.checkpoint(body)
    elif remat == "dots":
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

    (x, lb, zl), caches = jax.lax.scan(body, (x, 0.0, 0.0), params["layers"])
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = _unembed(params, cfg, x)
    cache = None
    if build_cache:
        cache = dict(caches)
        cache["pos"] = jnp.asarray(s, jnp.int32)
    return logits, cache, {"load_balance_loss": lb, "router_z_loss": zl}


def forward_chunk(params, cfg: ModelConfig, tokens: jax.Array, cache: PyTree,
                  *, attn_impl="chunked", kv_chunk=1024,
                  ) -> Tuple[jax.Array, PyTree]:
    """Continue a partial prefill: extend ``cache`` with ``tokens`` (B, C).

    The chunk occupies absolute positions ``cache["pos"] .. pos+C-1``. Per
    layer the chunk's K/V are written into the cache *first* (one
    ``dynamic_update_slice`` at the traced offset), then the chunk's queries
    attend over the whole cache lane with the causal mask keyed on absolute
    positions — rows at positions ≤ the query are exactly the real prefix
    (earlier chunks plus this one), rows beyond are masked out. This makes
    chunked prefill mathematically identical to a single full-prompt
    ``forward_seq`` for attention-family models.

    Supported families: DENSE / MOE / VLM (pure-attention token mixing).
    Recurrent families (SSM / HYBRID) and encoder-decoder models carry
    cross-chunk state that ``forward_seq`` does not externalize, so chunked
    continuation raises for them — the serving engine rejects the
    combination up front (``RealBackend.attach``).

    Requires an append-buffer cache (no ring wraparound): the caller must
    guarantee ``pos + C <= cache_len``; the serving engine enforces
    ``prompt_len <= cache_len`` when chunking is enabled.

    Returns (logits (B, C, V), new_cache). logits[:, -1] is the next-token
    distribution after the chunk — only meaningful to sample from on the
    final chunk of a prompt.
    """
    if cfg.family not in (DENSE, MOE, VLM) or cfg.is_encdec:
        raise NotImplementedError(
            f"forward_chunk supports attention-family models; {cfg.family}"
            f"{' enc-dec' if cfg.is_encdec else ''} carries recurrent "
            f"cross-chunk state")
    pos0 = cache["pos"]
    x = _embed(params, cfg, tokens, start_pos=pos0)
    b, c, _ = x.shape
    positions = positions_for(cfg, b, pos0, c)
    mrope_pos = (jnp.broadcast_to(positions[None], (3, b, c))
                 if cfg.pos_emb == "mrope" else None)
    layer_caches = {k: v for k, v in cache.items() if k != "pos"}

    def body(x, xs):
        lp, cache_l = xs
        new_cache = dict(cache_l)
        xn = apply_norm(lp["ln1"], x, cfg.norm)
        q, k, v = _qkv(lp["attn"], xn, cfg)
        q, k = _rope_qk(q, k, cfg, positions, mrope_pos)
        # append-buffer write at the chunk offset (pos0 is traced data, so
        # one compiled program serves every offset)
        ck = jax.lax.dynamic_update_slice(cache_l["k"], k, (0, pos0, 0, 0))
        cv = jax.lax.dynamic_update_slice(cache_l["v"], v, (0, pos0, 0, 0))
        w = ck.shape[1]
        pos_kv = jnp.broadcast_to(jnp.arange(w, dtype=jnp.int32)[None], (b, w))
        out = attention(q, ck, cv, positions, pos_kv, causal=True,
                        window=cfg.sliding_window, impl=attn_impl,
                        kv_chunk=kv_chunk)
        x = x + (out.reshape(b, c, -1) @ lp["attn"]["wo"]
                 + (lp["attn"]["bo"] if "bo" in lp["attn"] else 0))
        xn2 = apply_norm(lp["ln2"], x, cfg.norm)
        ffn_out, _ = _ffn(lp, xn2, cfg)
        x = x + ffn_out
        new_cache.update(k=ck, v=cv)
        return x, new_cache

    x, new_caches = jax.lax.scan(body, x, (params["layers"], layer_caches))
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = _unembed(params, cfg, x)
    new_cache = dict(new_caches)
    new_cache["pos"] = pos0 + c
    return logits, new_cache


def decode_step(params, cfg: ModelConfig, cache: PyTree, token: jax.Array,
                ) -> Tuple[jax.Array, PyTree]:
    """One decode step. token: (B,1) int32. Returns (logits (B,V), cache)."""
    pos = cache["pos"]
    x = _embed(params, cfg, token, start_pos=pos)
    layer_caches = {k: v for k, v in cache.items() if k != "pos"}

    def body(x, xs):
        lp, cache_l = xs
        x, new_cache, _ = layer_step(lp, x, cfg, cache_l, pos)
        return x, new_cache

    x, new_caches = jax.lax.scan(body, x, (params["layers"], layer_caches))
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = _unembed(params, cfg, x)[:, 0]
    new_cache = dict(new_caches)
    new_cache["pos"] = pos + 1
    return logits, new_cache


def decode_step_paged(params, cfg: ModelConfig, k_pool: jax.Array,
                      v_pool: jax.Array, table: jax.Array, pos, token: jax.Array,
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step of one sequence whose KV lives in block pools.

    ``k_pool``/``v_pool``: (N, L, bs, KH, dh), read only; ``table``:
    (W // bs,) the blocks of the sequence's W-row lane; ``pos``: scalar;
    ``token``: (1,) int32. Each layer gathers its own lane straight from the
    pool (``pool[table, l]``) and runs ``layer_step`` on it, so the math is
    ``decode_step``'s over the same values; only the row written at
    ``pos % W`` leaves the layer. Returns (logits (V,), k_rows (L, KH, dh),
    v_rows (L, KH, dh)); the caller writes the rows into the pools."""
    _, L, bs, kh, dh = k_pool.shape
    w = table.shape[0] * bs
    slot = jnp.mod(jnp.asarray(pos, jnp.int32), w)
    x = _embed(params, cfg, token[None], start_pos=pos)

    def body(x, xs):
        lp, l = xs
        with jax.named_scope("lane_gather"):
            lane = {"k": k_pool[table, l].reshape(1, w, kh, dh),
                    "v": v_pool[table, l].reshape(1, w, kh, dh)}
        x, new_lane, _ = layer_step(lp, x, cfg, lane, pos)
        return x, (new_lane["k"][0, slot], new_lane["v"][0, slot])

    x, (k_rows, v_rows) = jax.lax.scan(
        body, x, (params["layers"], jnp.arange(L, dtype=jnp.int32)))
    x = apply_norm(params["final_norm"], x, cfg.norm)
    logits = _unembed(params, cfg, x)[0, 0]
    return logits, k_rows, v_rows


# ===========================================================================
# Cache construction (decode entry without prefill — dry-run / fresh session)
# ===========================================================================
def init_cache(cfg: ModelConfig, batch: int, max_len: int, *, pos: int = 0,
               dtype=None) -> PyTree:
    """Allocate an (empty or positioned) decode cache pytree."""
    dt = jnp.dtype(dtype or cfg.dtype)
    L = cfg.num_layers
    c: Dict[str, Any] = {"pos": jnp.asarray(pos, jnp.int32)}
    if cfg.family == SSM:
        c["state"] = jnp.zeros((L, batch, cfg.num_heads, cfg.head_dim,
                                cfg.head_dim), jnp.float32)
        c["tm_prev"] = jnp.zeros((L, batch, cfg.d_model), dt)
        c["cm_prev"] = jnp.zeros((L, batch, cfg.d_model), dt)
        return c
    w = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    c["k"] = jnp.zeros((L, batch, w, cfg.num_kv_heads, cfg.head_dim), dt)
    c["v"] = jnp.zeros((L, batch, w, cfg.num_kv_heads, cfg.head_dim), dt)
    if cfg.family == HYBRID:
        d_inner = cfg.ssm.expand * cfg.d_model
        nh = d_inner // cfg.ssm.head_dim
        c["conv_tail"] = jnp.zeros((L, batch, cfg.ssm.conv_width - 1, d_inner), dt)
        c["ssm_state"] = jnp.zeros((L, batch, nh, cfg.ssm.state_size,
                                    cfg.ssm.head_dim), jnp.float32)
    if cfg.is_encdec:
        c["cross_k"] = jnp.zeros((L, batch, cfg.encoder_seq_len,
                                  cfg.num_kv_heads, cfg.head_dim), dt)
        c["cross_v"] = jnp.zeros((L, batch, cfg.encoder_seq_len,
                                  cfg.num_kv_heads, cfg.head_dim), dt)
    return c
