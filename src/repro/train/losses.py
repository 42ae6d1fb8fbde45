"""Losses: next-token cross entropy with padded-vocab + label masking."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def cross_entropy(
    logits: jax.Array,  # (B, S, V_padded)
    labels: jax.Array,  # (B, S) int32; -1 = ignore
    vocab_size: int,
) -> tuple[jax.Array, jax.Array]:
    """Returns (mean loss over valid tokens, valid-token count)."""
    Vp = logits.shape[-1]
    lf = logits.astype(jnp.float32)
    col = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    if Vp > vocab_size:  # mask Megatron-style vocab padding columns
        lf = jnp.where(col < vocab_size, lf, -1e9)
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    lse = jax.nn.logsumexp(lf, axis=-1)
    # the label's logit as a select-and-sum over the vocab axis (exact: one
    # term is non-zero), not a gather: XLA's SPMD partitioner aborts on a
    # gather over a sharded vocab inside the pod-manual shard_map
    picked = jnp.sum(jnp.where(col == safe[..., None], lf, 0.0), axis=-1)
    nll = jnp.where(valid, lse - picked, 0.0)
    count = jnp.maximum(valid.sum(), 1)
    return nll.sum() / count, count
