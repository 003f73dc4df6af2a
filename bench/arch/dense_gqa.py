"""Dense grouped-query attention with a plain MLP: the block of
``nemo4-15b`` and ``cmdr-32b``.

A sequential pre-LayerNorm block (eps 1e-5), rotate-half RoPE over the whole
head, grouped-query causal attention scaled by 1/sqrt(head_dim) with one K
and one V per KV head, a squared-ReLU (2-matrix) or gated-SiLU (3-matrix)
MLP, a final LayerNorm and an untied or tied head. This module is the one
place that knows it (see ``bench.arch``).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.reference import fp8, mm

#: Backend counters ``Shapes.decode`` reads: none, its work follows from
#: the contexts alone.
COUNTERS: Tuple[str, ...] = ()


# ---------------------------------------------------------- the program
def program_config(c: dict):
    """The program's ``ModelConfig``: its preset, cut as the file says; a
    width that disagrees with the file is an error, not a silent change."""
    from repro.configs.base import ACT_SILU, ACT_SQ_RELU, get_config
    act = {"silu": ACT_SILU, "relu2": ACT_SQ_RELU}[c["hidden_act"]]
    cfg = get_config(c["preset"]).replace(
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        dtype=c["dtype"])
    want = dict(activation=act, norm=c["norm"], use_bias=False,
                rope_theta=float(c["rope_theta"]), pos_emb="rope",
                tie_embeddings=c["tie_word_embeddings"], sliding_window=None,
                moe=None)
    for k, v in want.items():
        if getattr(cfg, k) != v:
            raise ValueError(f"preset {c['preset']} has {k}={getattr(cfg, k)}"
                             f", the configuration file states {v}")
    return cfg


# ------------------------------------------------------------ the weights
def layout(c: dict) -> dict:
    """{leaf path: (shape, std or 'one'/'zero')}, layers stacked."""
    d, L, dh = c["hidden_size"], c["num_hidden_layers"], c["head_dim"]
    h, kh, f, v = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["intermediate_size"], c["vocab_size"])
    out = {
        "embed": ((v, d), 0.02),
        "final_norm/scale": ((d,), "one"), "final_norm/bias": ((d,), "zero"),
        "layers/ln1/scale": ((L, d), "one"), "layers/ln1/bias": ((L, d), "zero"),
        "layers/ln2/scale": ((L, d), "one"), "layers/ln2/bias": ((L, d), "zero"),
        "layers/attn/wq": ((L, d, h * dh), d ** -0.5),
        "layers/attn/wk": ((L, d, kh * dh), d ** -0.5),
        "layers/attn/wv": ((L, d, kh * dh), d ** -0.5),
        "layers/attn/wo": ((L, h * dh, d), (h * dh) ** -0.5),
        "layers/mlp/w_up": ((L, d, f), d ** -0.5),
        "layers/mlp/w_down": ((L, f, d), f ** -0.5),
    }
    if c["hidden_act"] == "silu":
        out["layers/mlp/w_gate"] = ((L, d, f), d ** -0.5)
    if not c["tie_word_embeddings"]:
        out["lm_head"] = ((d, v), d ** -0.5)
    return out


# ------------------------------------------------------------ work counts
@dataclass(frozen=True)
class Shapes:
    """The sizes a step's work depends on (from a configuration file).

    The operations and bytes are what the algorithm needs, worked out from
    the sizes, not what the compiled program happens to do: a program that
    reads the KV lane past the live tokens, or copies the pool, shows as a
    lower share of the roofline, never as more work."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    gated: bool             # gated MLP: three matrices, else two
    tied: bool              # the head is the embedding
    dtype_bytes: int = 2

    @classmethod
    def from_config(cls, c: dict) -> "Shapes":
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   gated=c["hidden_act"] == "silu",
                   tied=c["tie_word_embeddings"],
                   dtype_bytes={"bfloat16": 2, "float32": 4}[c["dtype"]])

    # ---------------------------------------------------------- parameters
    @property
    def layer_params(self) -> int:
        d, dh = self.d_model, self.head_dim
        attn = 2 * d * self.heads * dh + 2 * d * self.kv_heads * dh
        mlp = (3 if self.gated else 2) * d * self.d_ff
        norms = 4 * d                      # two LayerNorms: scale and bias
        return attn + mlp + norms

    @property
    def embed_params(self) -> int:
        return self.vocab * self.d_model

    @property
    def head_params(self) -> int:
        return self.vocab * self.d_model

    def weight_bytes(self) -> dict:
        b = self.dtype_bytes
        layers = self.layers * self.layer_params * b
        table = (self.embed_params + (0 if self.tied else self.head_params)
                 + 2 * self.d_model) * b      # + final LayerNorm
        return {"layers": layers, "embedding_and_head": table,
                "total": layers + table}

    @property
    def kv_row(self) -> Tuple[int, ...]:
        """One token's entries in each of the program's two pools (K and
        V), layer first: the pools are (blocks, layers, block, kv_heads,
        head_dim)."""
        return (self.layers, self.kv_heads, self.head_dim)

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * self.dtype_bytes

    # ---------------------------------------------------------------- work
    def attn_flops(self, context: int) -> float:
        """Score and value products of one query over ``context`` keys."""
        return 4.0 * self.layers * self.heads * self.head_dim * context

    def token_flops(self) -> float:
        """Matrix products of one token through the layers (no head)."""
        return 2.0 * self.layers * (self.layer_params - 4 * self.d_model)

    def head_flops(self) -> float:
        return 2.0 * self.head_params

    def decode(self, contexts, counts=None) -> dict:
        """One decode step over a batch whose rows attend ``contexts`` keys:
        FLOPs, and bytes that must cross HBM (every weight the step uses
        once, the rows of the embedding it gathers, and each row's live KV).
        ``counts`` (the change of ``COUNTERS``) is not needed here."""
        contexts = list(contexts)
        n = len(contexts)
        flops = sum(self.token_flops() + self.head_flops()
                    + self.attn_flops(c) for c in contexts)
        w = self.weight_bytes()
        head = self.head_params * self.dtype_bytes
        weights = w["layers"] + head + 2 * self.d_model * self.dtype_bytes
        gathered = n * self.d_model * self.dtype_bytes
        kv = sum(contexts) * self.kv_bytes_per_token
        return {"flops": flops, "bytes": weights + gathered + kv}

    def prefill(self, start: int, end: int, emits: bool) -> float:
        """FLOPs of prompt positions [start, end) of one request, attending
        causally to every earlier position; the head only where a token is
        emitted."""
        n = end - start
        if n <= 0:
            return 0.0
        keys = n * start + n * (n + 1) / 2            # sum of (p + 1)
        flops = n * self.token_flops() + 4.0 * self.layers * self.heads \
            * self.head_dim * keys
        return flops + (self.head_flops() if emits else 0.0)


def shapes(c: dict) -> Shapes:
    return Shapes.from_config(c)


# ---------------------------------------------------------- the reference
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
    q = mm(h, p["attn/wq"], quant).reshape(t, dm.heads, dm.head_dim)
    k = mm(h, p["attn/wk"], quant).reshape(t, dm.kv_heads, dm.head_dim)
    v = mm(h, p["attn/wv"], quant).reshape(t, dm.kv_heads, dm.head_dim)
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
    x = x + mm(o, p["attn/wo"], quant)
    h = _norm(x) * p["ln2/scale"] + p["ln2/bias"]
    up = mm(h, p["mlp/w_up"], quant)
    if dm.gated:
        act = jax.nn.silu(mm(h, p["mlp/w_gate"], quant)) * up
    else:
        act = jnp.square(jax.nn.relu(up))
    return x + mm(act, p["mlp/w_down"], quant)


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
    return mm(h, w, quant)


class Reference:
    """The reference (or, with ``quant``, the float8 control) for one seed:
    the block above in straightforward ``jax.numpy`` at ``highest`` matmul
    precision, one sequence at a time with no cache, batching or kernels,
    every weight drawn again from the seed one layer or one block of the
    vocabulary at a time, in the layout the configuration's module gives."""

    #: vocabulary ids per head block (bounds the float32 head in memory)
    VOCAB_BLOCK = 32000

    def __init__(self, c: dict, seed: int, *, quant: bool = False):
        self.dm = Dims.of(c)
        self.lay = tuple((n, tuple(shape), init)
                         for n, (shape, init) in W.layout_of(c).items())
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
