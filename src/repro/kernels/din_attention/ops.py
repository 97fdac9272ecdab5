"""Public DIN-attention op with batch padding."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common import round_up
from repro.kernels.din_attention.kernel import din_attention_kernel


@functools.partial(jax.jit, static_argnames=("interpret",))
def din_attention(query, keys, mask, w1, b1, w2, b2, w3, b3, *,
                  interpret: bool = False):
    """query (B, D); keys (L, D); mask (L,). Returns (B, D)."""
    B = query.shape[0]
    bm = min(128, max(8, B))
    Bp = round_up(B, bm)
    qp = jnp.pad(query, ((0, Bp - B), (0, 0)))
    out = din_attention_kernel(qp, keys, mask, w1, b1, w2, b2, w3, b3,
                               bm=bm, interpret=interpret)
    return out[:B]
