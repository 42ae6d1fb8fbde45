"""Weight-side tables (the paper's method), as plain arithmetic.

Activations are held as ``bits``-wide two's-complement fixed point with
``frac`` fractional bits (round to nearest even, saturating).  Each run of
``chunk`` input rows of a weight matrix becomes a table of ``2**chunk``
entries, entry ``e`` the sum of the rows whose bit is set in ``e``.  The
entries of one table set (the members of one grouped projection) are
stored as integers of ``table_format`` under one power-of-two scale,
``2**ceil(log2(max|entry| / qmax))``.  A product is, over the bitplanes
``j`` of the codes, ``sum_j s_j * sum_c table[c, nibble_j(c)]`` with
``s_j = 2**(j - frac)`` and a negative most significant plane.  Here that
sum is an exact one-hot contraction in float32.

The control (``lower``) stores the tables one integer width below:
``i4`` for ``i8``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

QMAX = {"i16": 32767.0, "i8": 127.0, "i4": 7.0}
LOWER = {"i16": "i8", "i8": "i4"}


def _codes_onehot(x, bits, frac, chunk):
    """(N, q) -> (N, k, 2**chunk): sum over planes of s_j * onehot."""
    c = jnp.clip(jnp.round(x * 2.0**frac), -(2 ** (bits - 1)), 2 ** (bits - 1) - 1)
    u = jnp.where(c < 0, c + 2**bits, c).astype(jnp.int32)
    q = x.shape[-1]
    pad = -q % chunk
    u = jnp.pad(u, ((0, 0), (0, pad))).reshape(x.shape[0], -1, chunk)
    a = 0.0
    for j in range(bits):
        s = 2.0 ** (j - frac) * (-1.0 if j == bits - 1 else 1.0)
        nib = jnp.sum(((u >> j) & 1) << jnp.arange(chunk), axis=-1)
        a = a + s * jax.nn.one_hot(nib, 2**chunk, dtype=jnp.float32)
    return a


def _tables(w, chunk):
    q, p = w.shape
    pad = -q % chunk
    wc = jnp.pad(w, ((0, pad), (0, 0))).reshape(-1, chunk, p)
    e = jnp.arange(2**chunk)
    coeff = ((e[:, None] >> jnp.arange(chunk)[None, :]) & 1).astype(jnp.float32)
    return jnp.einsum("em,kmp->kep", coeff, wc)


def apply_set(ws, x, conf: dict, lower: bool):
    """Outputs of the members ``ws`` ((q, p) float32 each) of one table
    set, for activations ``x`` (..., q)."""
    fmt = LOWER[conf["table_format"]] if lower else conf["table_format"]
    qmax = QMAX[fmt]
    lead = x.shape[:-1]
    xf = x.reshape(-1, x.shape[-1])
    a = _codes_onehot(xf, conf["act_bits"], conf["act_frac"], conf["chunk"])
    tables = [_tables(w, conf["chunk"]) for w in ws]
    amax = jnp.max(jnp.stack([jnp.max(jnp.abs(t)) for t in tables]))
    scale = jnp.exp2(jnp.ceil(jnp.log2(jnp.maximum(amax, 1e-30) / qmax)))
    out = []
    for t in tables:
        tq = jnp.clip(jnp.round(t / scale), -qmax, qmax) * scale
        y = jnp.einsum("nke,kep->np", a, tq)
        out.append(y.reshape(*lead, -1))
    return out
