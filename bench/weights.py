"""Seeded weights, made on the device, leaf by leaf from a name.

Every leaf of a model is a function of ``(seed, leaf name, layer)`` alone,
drawn for the whole tree in one jitted call; the reference makes the same
call over the same names and shapes, so both hold the same values without
the reference ever touching what the program was given.

Names are the parameter paths joined by ``/`` (``blocks/wq``); a leaf
whose name starts with ``blocks/`` is stacked over layers on its first
axis. Scales: the input embedding 0.02, norm weights 0.1 (the model
multiplies by ``1 + w``), the output projection ``(vocab, d)``
``1/sqrt(d)``, every other matrix ``1/sqrt(fan_in)`` with fan-in on the
second-to-last axis.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

NORMS = ("ln1", "ln2", "ln_f", "q_norm", "k_norm")


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number below 2**64 (``PRNGKey`` keeps 32 bits)."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def leaf_scale(name: str, shape) -> float:
    last = name.rsplit("/", 1)[-1]
    if last in NORMS:
        return 0.1
    if last == "embed":
        return 0.02
    if last == "unembed":
        return float(1.0 / np.sqrt(shape[-1]))
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    return float(1.0 / np.sqrt(fan_in))


def leaf_key(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(name.encode()))


def draw(key: jax.Array, shape, scale: float, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def stacked(name: str) -> bool:
    return name.startswith("blocks/")


def tree_names(tree) -> list:
    """``/``-joined key paths of a nested dict's leaves, in flatten order."""
    out = []
    for path, _ in jax.tree_util.tree_leaves_with_path(tree):
        out.append("/".join(str(p.key) for p in path))
    return out


def make_params(key: jax.Array, shapes):
    """The whole parameter tree from ``shapes`` (a tree of
    ``ShapeDtypeStruct``), in their dtypes, in one jitted call."""
    names = tree_names(shapes)
    leaves, treedef = jax.tree.flatten(shapes)

    def build(key):
        out = []
        for name, sd in zip(names, leaves):
            if stacked(name):
                n, shape = sd.shape[0], sd.shape[1:]
                scale = leaf_scale(name, shape)
                lk = leaf_key(key, name)
                keys = jax.vmap(lambda i: jax.random.fold_in(lk, i))(
                    jnp.arange(n, dtype=jnp.uint32))
                out.append(jax.vmap(
                    lambda k: draw(k, shape, scale, sd.dtype))(keys))
            else:
                out.append(draw(leaf_key(key, name), sd.shape,
                                leaf_scale(name, sd.shape), sd.dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build)(key)
