"""Plain float32 ``jax.numpy`` forward of the llama decoder: RMSNorm,
half-split RoPE, causal grouped-query attention, SwiGLU, tied head.

It serves as the reference that decides ``correct``.  It imports nothing
of the program and takes nothing the program made: it makes the dense
weights again from the seed (``bench.weights``), one layer at a time, and
each projection applies the arithmetic the configuration states through
the family's module in this package (``wtab``, ``tl1``).  All matrix
products run at ``highest`` precision.
"""
from __future__ import annotations

import functools
import importlib
import math

import jax
import jax.numpy as jnp

from bench import weights
from bench.archs.llama import weight_layout


def _spec(c: dict, lower: bool) -> tuple:
    fam = c["family"]
    return (
        c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"],
        float(c["rope_theta"]), float(c["rms_norm_eps"]),
        fam, tuple(tuple(s) for s in c["linear_sets"]),
        tuple(sorted((k, v) for k, v in c[fam].items() if not isinstance(v, list))),
        bool(lower),
    )


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(t, theta):
    T, hd = t.shape[1], t.shape[-1]
    half = hd // 2
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * theta ** (
        -jnp.arange(half, dtype=jnp.float32) / half
    )
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    t1, t2 = t[..., :half], t[..., half:]
    return jnp.concatenate([t1 * cos - t2 * sin, t2 * cos + t1 * sin], axis=-1)


@functools.partial(jax.jit, static_argnames=("spec",))
def _block(x, w, spec):
    H, K, hd, theta, eps, fam, sets, conf, lower = spec
    apply_set = importlib.import_module(f"bench.reference.{fam}").apply_set
    conf = dict(conf)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    lin = {**w["attn"], **w["ffn"]}

    def project(h, names):
        out = {}
        for s in sets:
            if set(s) <= set(names):
                out.update(zip(s, apply_set([lin[m]["w"] for m in s], h, conf, lower)))
        return out

    N, T, _ = x.shape
    h = _rms(x, w["ln1"]["scale"], eps)
    a = project(h, ("wq", "wk", "wv"))
    q = _rope(a["wq"].reshape(N, T, H, hd), theta)
    k = _rope(a["wk"].reshape(N, T, K, hd), theta)
    v = a["wv"].reshape(N, T, K, hd)
    k, v = jnp.repeat(k, H // K, axis=2), jnp.repeat(v, H // K, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(N, T, H * hd)
    x = x + project(o, ("wo",))["wo"]
    h = _rms(x, w["ln2"]["scale"], eps)
    f = project(h, ("w_gate", "w_up"))
    g = jax.nn.silu(f["w_gate"]) * f["w_up"]
    return x + project(g, ("w_down",))["w_down"]


@jax.jit
def _embed(embed, tokens):
    return embed.astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnames=("eps",))
def _final(x, scale, eps):
    return _rms(x, scale.astype(jnp.float32), eps)


def hidden(c: dict, seed: int, tokens, lower: bool = False) -> jax.Array:
    """Final normed hidden states ``(N, T, d)`` of ``tokens`` ``(N, T)``.

    ``lower`` computes every projection one precision step below the one
    the configuration states: the control, which has to come out wrong.
    """
    layout = weight_layout(c)
    spec = _spec(c, lower)
    top = weights.top(layout, seed)
    with jax.default_matmul_precision("highest"):
        x = _embed(top["embed"], tokens)
        for i in range(c["num_hidden_layers"]):
            x = _block(x, weights.layer(layout, seed, i), spec)
        return _final(x, top["ln_f"]["scale"], eps=float(c["rms_norm_eps"]))


@jax.jit
def _stats(h, embed, targets):
    with jax.default_matmul_precision("highest"):
        logits = h @ embed.astype(jnp.float32).T
    best = jnp.max(logits, -1)
    at = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return best, at, jnp.argmax(logits, -1).astype(jnp.int32)


def head_stats(c: dict, seed: int, h, targets):
    """Per position: the best logit, the logit of ``targets`` and the
    argmax token, from the tied head in float32."""
    embed = weights.top(weight_layout(c), seed)["embed"]
    return _stats(h, embed, targets)
