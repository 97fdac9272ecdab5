"""Attention primitives: GQA (w/ RoPE, sliding window, KV cache), DIN target
attention, and the ranking-model cross attention from the paper's Fig. 1."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common import Array

NEG_INF = -1e30


def rope_freqs(head_dim: int, max_pos: int, theta: float = 10000.0) -> tuple[Array, Array]:
    """Returns (cos, sin) of shape (max_pos, head_dim//2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_pos, dtype=jnp.float32)
    ang = jnp.outer(t, inv)
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: Array, cos: Array, sin: Array, positions: Array) -> Array:
    """x: (..., S, H, D). positions: (..., S) int32 absolute positions."""
    c = jnp.take(cos, positions, axis=0)[..., None, :]  # (..., S, 1, D/2)
    s = jnp.take(sin, positions, axis=0)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def _repeat_kv(k: Array, groups: int) -> Array:
    """(B, S, Hkv, D) -> (B, S, Hkv*groups, D)."""
    if groups == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, groups, d)).reshape(b, s, h * groups, d)


def gqa_attention(
    q: Array,              # (B, Sq, Hq, D)
    k: Array,              # (B, Sk, Hkv, D)
    v: Array,              # (B, Sk, Hkv, D)
    *,
    causal: bool = True,
    window: int | None = None,          # sliding-window attention (Mistral/Mixtral)
    q_positions: Array | None = None,   # (B, Sq) absolute positions (decode offsets)
    kv_positions: Array | None = None,  # (B, Sk)
    kv_mask: Array | None = None,       # (B, Sk) bool valid mask (ring-buffer caches)
) -> Array:
    """Grouped-query scaled-dot attention. Returns (B, Sq, Hq, D)."""
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    assert hq % hkv == 0, (hq, hkv)
    k = _repeat_kv(k, hq // hkv)
    v = _repeat_kv(v, hq // hkv)

    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale

    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(sq)[None], (b, sq))
    if kv_positions is None:
        kv_positions = jnp.broadcast_to(jnp.arange(k.shape[1])[None], (b, k.shape[1]))
    dist = q_positions[:, :, None] - kv_positions[:, None, :]  # (B, Sq, Sk)
    mask = jnp.ones_like(dist, dtype=bool)
    if causal:
        mask &= dist >= 0
    if window is not None:
        mask &= dist < window
    if kv_mask is not None:
        mask &= kv_mask[:, None, :]
    logits = jnp.where(mask[:, None, :, :], logits, NEG_INF)

    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def target_attention(
    query: Array,      # (B, D)   candidate-item embedding (DIN target)
    keys: Array,       # (B, L, D) or (1, L, D) user history (broadcast over B)
    mask: Array,       # (B, L) or (1, L) bool valid positions
    mlp_apply,         # callable(x: (..., 4D)) -> (..., 1) attention MLP
) -> Array:
    """DIN local-activation unit (arXiv:1706.06978, Eq. 3): an MLP over
    [key, query, key-query, key*query] gives each history item's weight
    against the target, and the keys are sum-pooled by those weights. The
    weights are not normalized (no softmax, so they need not sum to 1: the
    paper keeps the strength of a user's interest); a masked slot weighs 0."""
    if keys.shape[0] == 1 and query.shape[0] != 1:
        keys = jnp.broadcast_to(keys, (query.shape[0],) + keys.shape[1:])
        mask = jnp.broadcast_to(mask, (query.shape[0],) + mask.shape[1:])
    q = jnp.broadcast_to(query[:, None, :], keys.shape)  # (B, L, D)
    feats = jnp.concatenate([keys, q, keys - q, keys * q], axis=-1)
    w = jnp.where(mask, mlp_apply(feats)[..., 0], 0.0)  # (B, L)
    return jnp.einsum("bl,bld->bd", w, keys)


def cross_attention(
    q: Array,          # (B, I, D) item-side queries
    k: Array,          # (1, L, D) user-sequence keys (computed ONCE — UOI)
    v: Array,          # (1, L, D)
    mask: Array | None = None,  # (1, L)
) -> Array:
    """Single-head candidate→user-history cross attention (paper Eq. 1).

    In UOI/MaRI, K/V carry batch 1 (user side, computed one-shot) and the
    einsum broadcasts — the tiled copy never materializes. In VanI, K/V
    arrive already tiled to B and the batched path is used.
    """
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    if k.shape[0] == 1 and q.shape[0] != 1:
        logits = jnp.einsum("bid,ld->bil", q, k[0]).astype(jnp.float32) * scale
    else:
        logits = jnp.einsum("bid,bld->bil", q, k).astype(jnp.float32) * scale
    if mask is not None:
        logits = jnp.where(mask[:, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if v.shape[0] == 1 and probs.shape[0] != 1:
        return jnp.einsum("bil,ld->bid", probs, v[0])
    return jnp.einsum("bil,bld->bid", probs, v)
