"""Weights from the seed, shared by the program's side and the plain
reference: a leaf is a pure function of ``(root key of the seed, leaf name, layer)``.

The builder hands these values to the program (in the dtype the
configuration stores them in); the reference, which imports nothing of
the program, calls the same functions with the same names after the
program's state is freed. Stacked per-layer leaves (``[L, ...]``) draw
layer ``l`` from ``fold_in(leaf key, l)``, so the reference can make one
layer at a time.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

INIT_STD = 0.02      # every matrix ~ N(0, 0.02), HF ``initializer_range``


def root_key(seed: int):
    """The key every leaf is folded from, as an array that jitted code
    takes as an argument (a seed closed over would compile anew for each
    seed). ``--seed`` may pass 2**31: two 31-bit words."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed % (1 << 31)),
                              seed >> 31)


def leaf_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


_BITS = {"bfloat16": (8, 7), "float16": (5, 10)}


def round_through(x, dtype):
    """float32 values rounded to what ``dtype`` can hold, kept float32.
    ``reduce_precision`` and not a pair of converts: XLA may drop a
    down-and-up conversion (``xla_allow_excess_precision``) and on the
    TPU does."""
    bits = _BITS.get(jnp.dtype(dtype).name)
    return x if bits is None else jax.lax.reduce_precision(x, *bits)


def is_norm(name: str) -> bool:
    return "norm" in name


def layer_leaf(key, name: str, layer, shape, dtype):
    """One layer's slice of a stacked leaf (``shape`` without the layer
    axis) in ``dtype``."""
    return layer_leaf_f32(key, name, layer, shape, dtype).astype(dtype)


def layer_leaf_f32(key, name: str, layer, shape, dtype):
    """The same values as float32 (what the reference computes with)."""
    if is_norm(name):
        return jnp.ones(shape, jnp.float32)
    key = jax.random.fold_in(leaf_key(key, name), layer)
    return round_through(
        jax.random.normal(key, shape, jnp.float32) * INIT_STD, dtype)


def leaf(key, name: str, shape, dtype, stacked: bool):
    """A whole leaf; ``stacked`` leaves have the layer axis first."""
    if not stacked:
        return layer_leaf(key, name, 0, shape, dtype)
    return jax.vmap(lambda l: layer_leaf(key, name, l, shape[1:], dtype))(
        jnp.arange(shape[0]))
