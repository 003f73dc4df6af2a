"""Weights drawn from the seed by the benchmark, on the device, in one call.

Every element is a counter-based hash of (leaf salt, flat index), so the
whole tree is one fused, jitted program with no random-bit buffers, the same
values come out on any backend, and the plain reference can draw the same
weights again without taking anything the program made. The salts depend on
the seed and the leaf's name only; the seed enters as data, so a new seed
compiles nothing.

The tree has the layout the serving program consumes, as the configuration's
architecture module gives it (``bench.arch``). Each matrix is uniform with
the standard deviation the program's own initializer gives it
(1/sqrt(fan-in); 0.02 for the embedding); norms start at scale 1 and bias 0,
as the program's initializer has them.
"""
from __future__ import annotations

import functools
import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np


def layout_of(c: dict) -> dict:
    """{leaf path: (shape, std or 'one'/'zero')}: the layout of the module
    that the configuration names (``bench.arch``). ``make`` and every
    reference draw from it."""
    from bench import arch
    return arch.load(c).layout(c)


def salts(names, seed: int) -> np.ndarray:
    """One 32-bit salt per leaf from the seed (any size of integer)."""
    return np.asarray([int.from_bytes(hashlib.blake2b(
        f"{seed}/{n}".encode(), digest_size=4).digest(), "little")
        for n in names], np.uint32)


def _mix(x):
    """lowbias32 finaliser: a bijection of uint32 with good avalanche."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def uniform(full_shape, salt, start=None, size=None):
    """Uniform [-1, 1) float32: the block of a ``full_shape`` leaf that
    starts at ``start`` (ints, may be traced) and has shape ``size`` (the
    whole leaf by default). Element i of the leaf is hashed from (salt, i),
    so a block drawn alone equals the same block of the whole."""
    size = tuple(size or full_shape)
    if math.prod(full_shape) >= 2 ** 32:
        raise ValueError(f"leaf {full_shape} overflows the 32-bit index")
    start = start or (0,) * len(full_shape)
    idx = jnp.zeros(size, jnp.uint32)
    stride = 1
    for ax in reversed(range(len(full_shape))):
        pos = jax.lax.broadcasted_iota(jnp.uint32, size, ax) \
            + jnp.asarray(start[ax]).astype(jnp.uint32)
        idx = idx + pos * jnp.uint32(stride)
        stride *= full_shape[ax]
    return _centred(idx, salt)


def _centred(idx, salt):
    """Hash ``idx`` to a whole number in [-2**23, 2**23), times 2**-23: both
    steps are exact in float32, so one multiply by the leaf's scale is the
    only rounding, whatever the compiler fuses."""
    x = _mix(_mix(idx * jnp.uint32(0x9E3779B1) + salt) ^ salt)
    v = (x >> 8).astype(jnp.int32) - (1 << 23)
    return v.astype(jnp.float32) * (1.0 / (1 << 23))


def rows(full_shape, salt, row_ids):
    """Rows ``row_ids`` of a 2-D leaf (an embedding gather)."""
    n = full_shape[1]
    idx = (row_ids.astype(jnp.uint32)[:, None] * jnp.uint32(n)
           + jax.lax.broadcasted_iota(jnp.uint32, (row_ids.shape[0], n), 1))
    return _centred(idx, salt)


def leaf(shape, init, salt, dtype, start=None, size=None):
    """A leaf (or a block of it) in ``dtype``."""
    size = tuple(size or shape)
    if init == "one":
        return jnp.ones(size, dtype)
    if init == "zero":
        return jnp.zeros(size, dtype)
    return (uniform(shape, salt, start, size)
            * (math.sqrt(3.0) * init)).astype(dtype)


def nest(flat: dict) -> dict:
    tree: dict = {}
    for path, x in flat.items():
        *head, last = path.split("/")
        node = tree
        for k in head:
            node = node.setdefault(k, {})
        node[last] = x
    return tree


@functools.lru_cache(maxsize=None)
def _program(spec: tuple, dtype: str):
    names = [n for n, _ in spec]

    @jax.jit
    def make(salt_arr):
        return nest({n: leaf(shape, init, salt_arr[i], dtype)
                     for i, (n, (shape, init)) in enumerate(spec)})
    return names, make


def make(c: dict, seed: int):
    """The configuration's weights for ``seed``, on the default device."""
    spec = tuple((n, (tuple(s), i)) for n, (s, i) in layout_of(c).items())
    names, program = _program(spec, c["dtype"])
    return program(jnp.asarray(salts(names, seed)))
