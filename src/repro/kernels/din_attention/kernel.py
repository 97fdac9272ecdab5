"""DIN target attention as one fused TPU Pallas kernel.

The whole local-activation unit — [k,q,k-q,k*q] features, 3-layer scoring
MLP, its unnormalized outputs as the history's weights (arXiv:1706.06978,
Eq. 3; masked slots weigh 0), weighted pool — runs per batch tile
entirely in VMEM. The user history (L×D, one-shot under UOI) and the tiny
MLP weights are broadcast to every grid step; the (B, L, 4D) feature tensor
never reaches HBM. This is the serving-side fusion the paper's engine would
apply on GPU, re-blocked for VMEM/MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(q_ref, k_ref, m_ref, w1_ref, b1_ref, w2_ref, b2_ref, w3_ref,
            b3_ref, o_ref):
    q = q_ref[...]                       # (bm, D)
    keys = k_ref[...]                    # (L, D)
    bm, D = q.shape
    L = keys.shape[0]
    k = jnp.broadcast_to(keys[None], (bm, L, D))
    qe = jnp.broadcast_to(q[:, None, :], (bm, L, D))
    feats = jnp.concatenate([k, qe, k - qe, k * qe], axis=-1)   # (bm, L, 4D)
    flat = feats.reshape(bm * L, 4 * D)
    h = jax.nn.relu(jnp.dot(flat, w1_ref[...],
                            preferred_element_type=jnp.float32) + b1_ref[...])
    h = jax.nn.relu(jnp.dot(h, w2_ref[...],
                            preferred_element_type=jnp.float32) + b2_ref[...])
    s = (jnp.dot(h, w3_ref[...], preferred_element_type=jnp.float32)
         + b3_ref[...]).reshape(bm, L)
    p = jnp.where(m_ref[...][None, :] != 0, s, 0.0)
    o_ref[...] = jnp.dot(p.astype(keys.dtype), keys,
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def din_attention_kernel(query, keys, mask, w1, b1, w2, b2, w3, b3, *,
                         bm: int = 128, interpret: bool = False):
    B, D = query.shape
    L = keys.shape[0]
    h1, h2 = w1.shape[1], w2.shape[1]
    assert B % bm == 0
    mask_i = mask.astype(jnp.int32)
    full = lambda *shape: (shape, lambda i: tuple(0 for _ in shape))

    def spec(shape, imap):
        return pl.BlockSpec(shape, imap)

    return pl.pallas_call(
        _kernel,
        grid=(B // bm,),
        in_specs=[
            spec((bm, D), lambda i: (i, 0)),
            spec((L, D), lambda i: (0, 0)),
            spec((L,), lambda i: (0,)),
            spec((4 * D, h1), lambda i: (0, 0)),
            spec((h1,), lambda i: (0,)),
            spec((h1, h2), lambda i: (0, 0)),
            spec((h2,), lambda i: (0,)),
            spec((h2, 1), lambda i: (0, 0)),
            spec((1,), lambda i: (0,)),
        ],
        out_specs=spec((bm, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, D), query.dtype),
        interpret=interpret,
    )(query, keys, mask_i, w1, b1, w2, b2, w3, b3)
