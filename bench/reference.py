"""The plain reference: the configuration's forward pass, in float32.

It follows the equations the configuration file states -- a sequential
pre-LayerNorm block (eps 1e-5), rotate-half RoPE over the whole head,
grouped-query causal attention scaled by 1/sqrt(head_dim), a squared-ReLU or
gated-SiLU MLP, a final LayerNorm and an untied or tied head -- in
straightforward ``jax.numpy`` at ``highest`` matmul precision, one sequence
at a time with no cache, batching or kernels. It imports nothing of the
program: it tokenizes the prompt itself and draws every weight again from
the seed (``bench.weights``), one layer or one block of the vocabulary at a
time, so it runs in little memory once the program's state is freed.

``quant=True`` is the control: the same pass with every matrix product's
operands (weights, activations, queries, keys and values) rounded to float8
e4m3 with one scale per tensor -- the precision below the configuration's
bfloat16 that a later change could be tempted to serve in.
"""
from __future__ import annotations

import functools
import math
import re
from typing import List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

PAD, CLS, N_SPECIAL = 0, 1, 3
_WORD = re.compile(r"[a-z0-9]+")


# ------------------------------------------------------------- the input
def _fnv1a(word: str) -> int:
    h = 0xcbf29ce484222325
    for ch in word.encode():
        h = ((h ^ ch) * 0x100000001b3) & 0xFFFFFFFFFFFFFFFF
    return h


def encode(prompt: str, vocab: int, max_len: int) -> List[int]:
    """Word-hash token ids: [CLS] then FNV-1a of each lower-cased word."""
    ids = [CLS] + [N_SPECIAL + _fnv1a(w) % (vocab - N_SPECIAL)
                   for w in _WORD.findall(prompt.lower())]
    return ids[:max_len]


def bucket(n: int, prompt_len: int, min_bucket: int = 8) -> int:
    """The served prompt length: ``n`` padded with id 0 to a power of two,
    at least ``min_bucket`` and at most ``prompt_len``."""
    b = 1 << max(max(n, min(min_bucket, prompt_len)) - 1, 0).bit_length()
    return min(prompt_len, b)


def served_sequence(prompt: str, served: Sequence[int], vocab: int,
                    prompt_len: int):
    """Token ids the model sees (padded prompt, then every served token but
    the last) and the position whose logits choose served token 0."""
    ids = encode(prompt, vocab, prompt_len)
    p = bucket(len(ids), prompt_len)
    seq = ids + [PAD] * (p - len(ids)) + list(served[:-1])
    return np.asarray(seq, np.int32), p - 1


# ------------------------------------------------------------ the model
class Dims(NamedTuple):
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool
    tied: bool
    theta: float
    dtype: str

    @classmethod
    def of(cls, c: dict) -> "Dims":
        return cls(c["num_hidden_layers"], c["hidden_size"],
                   c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"], c["intermediate_size"], c["vocab_size"],
                   c["hidden_act"] == "silu", c["tie_word_embeddings"],
                   float(c["rope_theta"]), c["dtype"])


def fp8(x):
    """Round to float8 e4m3 (3 mantissa bits, max 448, subnormals below
    2**-6) with one scale per tensor that maps its largest magnitude to 448."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    y = x / s
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -6)))
    step = 2.0 ** (e - 3)
    return jnp.clip(jnp.round(y / step) * step, -448.0, 448.0) * s


def _mm(a, b, quant):
    if quant:
        a, b = fp8(a), fp8(b)
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def _norm(x, eps=1e-5):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def _rope(x, positions, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / x.shape[-1])
    ang = positions[:, None, None].astype(jnp.float32) * freqs
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x1 * jnp.sin(ang) + x2 * jnp.cos(ang)], -1)


Layout = Tuple[Tuple[str, tuple, object], ...]   # (leaf, shape, init)


def _leaf(lay: Layout, salts, dtype: str, name: str, start=None, size=None):
    """A leaf, or a block of it, as the program holds it (``dtype``), in
    float32."""
    i = [n for n, _, _ in lay].index(name)
    _, shape, init = lay[i]
    return W.leaf(shape, init, salts[i], dtype, start, size).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dm", "lay", "quant"))
def _block(x, l, salts, *, dm: Dims, lay: Layout, quant: bool):
    """Layer ``l`` over the whole sequence x: (T, d) float32."""
    p = {n.split("/", 1)[1]: _leaf(lay, salts, dm.dtype, n,
                                   (l,) + (0,) * (len(shape) - 1),
                                   (1,) + shape[1:])[0]
         for n, shape, _ in lay if n.startswith("layers/")}
    t = x.shape[0]
    pos = jnp.arange(t)
    h = _norm(x) * p["ln1/scale"] + p["ln1/bias"]
    q = _mm(h, p["attn/wq"], quant).reshape(t, dm.heads, dm.head_dim)
    k = _mm(h, p["attn/wk"], quant).reshape(t, dm.kv_heads, dm.head_dim)
    v = _mm(h, p["attn/wv"], quant).reshape(t, dm.kv_heads, dm.head_dim)
    q, k = _rope(q, pos, dm.theta), _rope(k, pos, dm.theta)
    if quant:
        q, k, v = fp8(q), fp8(k), fp8(v)
    g = dm.heads // dm.kv_heads
    qg = q.reshape(t, dm.kv_heads, g, dm.head_dim)
    s = jnp.einsum("qkgd,skd->kgqs", qg, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(dm.head_dim)
    s = jnp.where(pos[None, None, :, None] >= pos[None, None, None, :], s,
                  -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("kgqs,skd->qkgd", a, v,
                   precision=jax.lax.Precision.HIGHEST).reshape(t, -1)
    x = x + _mm(o, p["attn/wo"], quant)
    h = _norm(x) * p["ln2/scale"] + p["ln2/bias"]
    up = _mm(h, p["mlp/w_up"], quant)
    if dm.gated:
        act = jax.nn.silu(_mm(h, p["mlp/w_gate"], quant)) * up
    else:
        act = jnp.square(jax.nn.relu(up))
    return x + _mm(act, p["mlp/w_down"], quant)


@functools.partial(jax.jit, static_argnames=("dm", "lay"))
def _embed(tokens, salts, *, dm: Dims, lay: Layout):
    i = [n for n, _, _ in lay].index("embed")
    _, shape, std = lay[i]
    x = W.rows(shape, salts[i], tokens) * (math.sqrt(3.0) * std)
    return x.astype(dm.dtype).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("dm", "lay"))
def _final(x, salts, *, dm: Dims, lay: Layout):
    return (_norm(x) * _leaf(lay, salts, dm.dtype, "final_norm/scale")
            + _leaf(lay, salts, dm.dtype, "final_norm/bias"))


@functools.partial(jax.jit, static_argnames=("dm", "lay", "quant", "width"))
def _head_block(h, j0, salts, *, dm: Dims, lay: Layout, quant: bool,
                width: int):
    """Logits of rows ``h`` for vocabulary ids [j0, j0 + width)."""
    if dm.tied:
        w = _leaf(lay, salts, dm.dtype, "embed", (j0, 0), (width, dm.d)).T
    else:
        w = _leaf(lay, salts, dm.dtype, "lm_head", (0, j0), (dm.d, width))
    return _mm(h, w, quant)


class Reference:
    """The reference (or, with ``quant``, the control) for one seed."""

    #: vocabulary ids per head block (bounds the float32 head in memory)
    VOCAB_BLOCK = 32000

    def __init__(self, c: dict, seed: int, *, quant: bool = False):
        self.dm = Dims.of(c)
        self.lay = tuple((n, tuple(shape), init)
                         for n, (shape, init) in W.layout(c).items())
        self.salts = jnp.asarray(W.salts([n for n, _, _ in self.lay], seed))
        self.quant = quant

    def logits(self, tokens: np.ndarray, rows: np.ndarray) -> np.ndarray:
        """Float32 logits (len(rows), vocab) at positions ``rows`` of the
        sequence ``tokens`` (padded to a multiple of 512 positions; padding
        sits after every row read, so causal attention never sees it)."""
        dm, kw = self.dm, dict(dm=self.dm, lay=self.lay)
        t = len(tokens)
        padded = np.zeros(-(-t // 512) * 512, np.int32)
        padded[:t] = tokens
        x = _embed(jnp.asarray(padded), self.salts, **kw)
        for l in range(dm.layers):
            x = _block(x, l, self.salts, quant=self.quant, **kw)
        # rows padded to a multiple of 128 (the last one repeated, which no
        # per-tensor scale sees) so that few head programs compile
        n = len(rows)
        rows = np.concatenate([rows, np.full(-n % 128, rows[-1])])
        h = _final(x[jnp.asarray(rows)], self.salts, **kw)
        width = min(self.VOCAB_BLOCK, dm.vocab)
        out = [_head_block(h, j0, self.salts, quant=self.quant, width=width,
                           **kw)
               for j0 in range(0, dm.vocab - width + 1, width)]
        if dm.vocab % width:
            out.append(_head_block(h, dm.vocab - dm.vocab % width, self.salts,
                                   quant=self.quant, width=dm.vocab % width,
                                   **kw))
        return np.asarray(jnp.concatenate(out, axis=1))[:n]


def gaps(ref_logits: np.ndarray, chosen: Sequence[int]) -> np.ndarray:
    """How far below the reference's best logit each chosen token lies."""
    lg = np.asarray(ref_logits, np.float64)
    return lg.max(axis=1) - lg[np.arange(len(chosen)), np.asarray(chosen)]
