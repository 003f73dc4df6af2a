"""What every architecture's plain reference shares.

The reference itself is the configuration's forward pass in float32, and
lives in its architecture module (``bench.arch``): it imports nothing of the
program, tokenizes the prompt itself and draws every weight again from the
seed (``bench.weights``). Shared here: the tokenizer and the served sequence
it sees, the matrix product at ``highest`` precision, the control's
precision, and the gap the check compares.

The control (``quant=True`` in a ``Reference``) rounds every matrix
product's operands (weights, activations, queries, keys and values) to
float8 e4m3 with one scale per tensor -- the precision below the
configuration's bfloat16 that a later change could be tempted to serve in.
"""
from __future__ import annotations

import re
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

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


# ------------------------------------------------------- the arithmetic
def fp8(x):
    """Round to float8 e4m3 (3 mantissa bits, max 448, subnormals below
    2**-6) with one scale per tensor that maps its largest magnitude to 448."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    y = x / s
    e = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(y), 2.0 ** -6)))
    step = 2.0 ** (e - 3)
    return jnp.clip(jnp.round(y / step) * step, -448.0, 448.0) * s


def mm(a, b, quant):
    """``a @ b`` in float32 at ``highest`` precision; with ``quant`` the
    operands are rounded to float8 first."""
    if quant:
        a, b = fp8(a), fp8(b)
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


def gaps(ref_logits: np.ndarray, chosen: Sequence[int]) -> np.ndarray:
    """How far below the reference's best logit each chosen token lies."""
    lg = np.asarray(ref_logits, np.float64)
    return lg.max(axis=1) - lg[np.arange(len(chosen)), np.asarray(chosen)]
