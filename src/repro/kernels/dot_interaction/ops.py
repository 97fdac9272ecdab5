"""Public dot-interaction op with batch padding."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common import round_up
from repro.kernels.dot_interaction.kernel import dot_interaction_kernel


@functools.partial(jax.jit, static_argnames=("keep_self", "interpret"))
def dot_interaction(x, *, keep_self: bool = False, interpret: bool = False):
    """x (B, F, D) -> (B, F*(F±1)/2) pairwise dots (DLRM interaction)."""
    B = x.shape[0]
    bm = min(128, max(8, B))
    Bp = round_up(B, bm)
    xp = jnp.pad(x, ((0, Bp - B), (0, 0), (0, 0)))
    out = dot_interaction_kernel(xp, keep_self=keep_self, bm=bm,
                                 interpret=interpret)
    return out[:B]
