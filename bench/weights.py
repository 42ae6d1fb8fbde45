"""Dense weights from ``--seed``, made on the device in one jitted call.

The layers of a model sit in stacks, ``{prefix: depth}``
(``spec.stacks``): every leaf under ``<prefix>/`` is one layer's, and the
tree holds it ``depth`` deep.  Leaf ``j`` of the sorted layout draws from
``fold_in(key, j)``, and layer ``i`` of a stacked leaf from
``fold_in(fold_in(key, j), i)``, so one layer can be made again on its
own (:func:`layer`), value for value, by the reference after the window.
Nothing here imports the program.
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


def _stack_of(path: str, prefixes) -> str | None:
    return next((p for p in prefixes if path.startswith(p + "/")), None)


@functools.partial(jax.jit, static_argnames=("layout", "stacks", "dtype"))
def _make(key, layout, stacks, dtype):
    depth = dict(stacks)
    flat = {}
    for j, (path, shape, init) in enumerate(layout):
        k = jax.random.fold_in(key, j)
        prefix = _stack_of(path, depth)
        if prefix is not None:
            ks = jax.vmap(lambda i: jax.random.fold_in(k, i))(jnp.arange(depth[prefix]))
            flat[path] = jax.vmap(lambda kk: _leaf(kk, shape, init, dtype))(ks)
        else:
            flat[path] = _leaf(k, shape, init, dtype)
    return _nest(flat)


@functools.partial(jax.jit, static_argnames=("layout", "prefix", "dtype"))
def _make_layer(key, i, layout, prefix, dtype):
    flat = {}
    for j, (path, shape, init) in enumerate(layout):
        if path.startswith(prefix + "/"):
            k = jax.random.fold_in(jax.random.fold_in(key, j), i)
            flat[path[len(prefix) + 1:]] = _leaf(k, shape, init, dtype)
    return _nest(flat)


@functools.partial(jax.jit, static_argnames=("layout", "stacks", "dtype"))
def _make_top(key, layout, stacks, dtype):
    return _nest({
        path: _leaf(jax.random.fold_in(key, j), shape, init, dtype)
        for j, (path, shape, init) in enumerate(layout)
        if _stack_of(path, stacks) is None
    })


def _frozen(layout: dict) -> tuple:
    return tuple((p, tuple(s), i) for p, (s, i) in sorted(layout.items()))


def make(layout: dict, stacks: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """The whole tree, each leaf under ``<prefix>/`` stacked
    ``(stacks[prefix], ...)``."""
    return _make(base_key(seed), _frozen(layout), tuple(sorted(stacks.items())), dtype)


def layer(layout: dict, seed: int, i: int, prefix: str = "blocks",
          dtype=jnp.bfloat16) -> dict:
    """Layer ``i`` of stack ``prefix``, equal to
    ``make(...)[prefix][...][i]``."""
    return _make_layer(base_key(seed), jnp.int32(i), _frozen(layout), prefix, dtype)


def top(layout: dict, seed: int, stacks=("blocks",), dtype=jnp.bfloat16) -> dict:
    """The leaves outside every stack (embedding, final norm, head);
    ``stacks`` holds the prefixes (a ``{prefix: depth}`` will do)."""
    return _make_top(base_key(seed), _frozen(layout), tuple(sorted(stacks)), dtype)
