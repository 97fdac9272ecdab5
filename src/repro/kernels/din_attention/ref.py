"""Pure-jnp oracle: DIN local-activation target attention."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def din_attention_ref(query, keys, mask, w1, b1, w2, b2, w3, b3):
    """query (B, D); keys (L, D); mask (L,) bool. MLP: 4D->h1->h2->1 (relu).
    Returns (B, D) interest vector: keys pooled by the MLP's unnormalized
    outputs (arXiv:1706.06978, Eq. 3), masked slots weighing 0."""
    B, D = query.shape
    L = keys.shape[0]
    k = jnp.broadcast_to(keys[None], (B, L, D))
    q = jnp.broadcast_to(query[:, None, :], (B, L, D))
    feats = jnp.concatenate([k, q, k - q, k * q], axis=-1)      # (B, L, 4D)
    h = jax.nn.relu(feats @ w1 + b1)
    h = jax.nn.relu(h @ w2 + b2)
    w = jnp.where(mask[None, :], (h @ w3 + b3)[..., 0], 0.0)     # (B, L)
    return jnp.einsum("bl,ld->bd", w, keys)
