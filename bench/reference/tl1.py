"""TL1 activation-side tables, as plain arithmetic.

Each weight matrix is ternarised on its own: ``t = clip(round(w / mean|w|),
-1, 1)``, with the scale refitted by least squares, ``s = <w, t> / <t, t>``.
Activations are quantized per token to ``act_bits``-wide symmetric
integers under the absmax scale ``max|x| / (2**(act_bits-1) - 1)``.  The
product is then ``(codes @ t) * s_a * s``, exact in float32: what the
tables sum by additions alone.

The control (``lower``) quantizes the activations one integer width
below: int4 for int8.
"""
from __future__ import annotations

import jax.numpy as jnp

LOWER = {8: 4}


def ternary(w):
    s0 = jnp.maximum(jnp.mean(jnp.abs(w)), 1e-12)
    t = jnp.clip(jnp.round(w / s0), -1.0, 1.0)
    return t, jnp.sum(w * t) / jnp.maximum(jnp.sum(t * t), 1.0)


def apply_set(ws, x, conf: dict, lower: bool):
    bits = LOWER[conf["act_bits"]] if lower else conf["act_bits"]
    qmax = float(2 ** (bits - 1) - 1)
    sa = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-12) / qmax
    codes = jnp.clip(jnp.round(x / sa), -qmax, qmax)
    out = []
    for w in ws:
        t, s = ternary(w)
        out.append((codes @ t) * sa * s)
    return out
