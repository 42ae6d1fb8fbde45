"""Dense weights from ``--seed``, made on the device in one jitted call.

Leaf ``j`` of the sorted layout draws from ``fold_in(key, j)``, and layer
``i`` of a block leaf from ``fold_in(fold_in(key, j), i)``, so one layer
can be made again on its own (:func:`layer`), value for value, by the
reference after the window.  Nothing here imports the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


def base_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.PRNGKey(word)


def _leaf(key, shape, init, dtype):
    if init == "ones":
        return jnp.ones(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * init).astype(dtype)


def _nest(flat: dict) -> dict:
    out: dict = {}
    for path, v in flat.items():
        node = out
        *head, last = path.split("/")
        for h in head:
            node = node.setdefault(h, {})
        node[last] = v
    return out


@functools.partial(jax.jit, static_argnames=("layout", "layers", "dtype"))
def _make(key, layout, layers, dtype):
    flat = {}
    for j, (path, shape, init) in enumerate(layout):
        k = jax.random.fold_in(key, j)
        if path.startswith("blocks/"):
            ks = jax.vmap(lambda i: jax.random.fold_in(k, i))(jnp.arange(layers))
            flat[path] = jax.vmap(lambda kk: _leaf(kk, shape, init, dtype))(ks)
        else:
            flat[path] = _leaf(k, shape, init, dtype)
    return _nest(flat)


@functools.partial(jax.jit, static_argnames=("layout", "dtype"))
def _make_layer(key, i, layout, dtype):
    flat = {}
    for j, (path, shape, init) in enumerate(layout):
        if path.startswith("blocks/"):
            k = jax.random.fold_in(jax.random.fold_in(key, j), i)
            flat[path[len("blocks/"):]] = _leaf(k, shape, init, dtype)
    return _nest(flat)


@functools.partial(jax.jit, static_argnames=("layout", "dtype"))
def _make_top(key, layout, dtype):
    return _nest({
        path: _leaf(jax.random.fold_in(key, j), shape, init, dtype)
        for j, (path, shape, init) in enumerate(layout)
        if not path.startswith("blocks/")
    })


def _frozen(layout: dict) -> tuple:
    return tuple((p, tuple(s), i) for p, (s, i) in sorted(layout.items()))


def make(layout: dict, layers: int, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole tree, block leaves stacked ``(layers, ...)``."""
    return _make(base_key(seed), _frozen(layout), layers, dtype)


def layer(layout: dict, seed: int, i: int, dtype=jnp.bfloat16) -> dict:
    """Block ``i``'s leaves, equal to ``make(...)["blocks"][...][i]``."""
    return _make_layer(base_key(seed), jnp.int32(i), _frozen(layout), dtype)


def top(layout: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The leaves outside the blocks (embedding, final norm)."""
    return _make_top(base_key(seed), _frozen(layout), dtype)
