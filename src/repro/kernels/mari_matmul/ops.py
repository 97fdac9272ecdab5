"""Public fused-MaRI matmul ops: pad to MXU-aligned tiles, compute the tiny
user-side products with jnp (2·Du·d FLOPs), and dispatch the Pallas kernel
for the batched side with the user row fused as accumulator init and the
bias + activation applied in the kernel epilogue.

``mari_matmul_fused``        — Eq. 7 two-group form (user, rest).
``mari_matmul_fused_groups`` — multi-group / fragmented form: any number of
    (x, w) products summed into one output. Batch-1 operands (user side,
    Σ 2·Du·d FLOPs) fold into the accumulator-init row; batch-B operands
    concatenate into a single MXU stream (Σ_g x_g @ w_g == concat(x_g) @
    stack(w_g), the block-matmul identity of Eq. 2), so a §2.4-fragmented
    layout costs one kernel launch, not one per fragment.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common import round_up
from repro.kernels.mari_matmul.kernel import (_EPILOGUES, mari_matmul_kernel,
                                              mari_matmul_kernel_gather)

_VMEM_BUDGET = 8 * 1024 * 1024  # bytes; conservative half of v5e VMEM


def _pick_blocks(B: int, Dr: int, d: int, itemsize: int) -> tuple[int, int, int]:
    bm = min(256, round_up(min(B, 256), 8))
    bn = min(256, round_up(min(d, 256), 128))
    bk = 512
    while (bm * bk + bk * bn) * itemsize + bm * bn * 4 > _VMEM_BUDGET and bk > 128:
        bk //= 2
    return bm, bn, bk


@functools.partial(jax.jit, static_argnames=("activation", "interpret"))
def mari_matmul_fused_groups(parts, b=None, *, acc0=None, user_index=None,
                             activation="identity", interpret=False):
    """act(Σ_g Tile-or-stream(x_g @ w_g) + acc0 + b) for (x, w) pairs.

    Each x is (1, D_g) (user side — folded into the broadcast row) or
    (B, D_g) (batched side — streamed through the MXU). ``acc0`` is an
    optional precomputed partial added to the accumulator init — a (1, d)
    row (one user per batch) or a row-wise (B, d) block (cross-user
    coalesced serving: row b carries user b's partial). With
    ``user_index`` (B,), ``acc0`` is instead the STACKED (U, d) per-user
    table and the kernel gathers row ``user_index[b]`` at accumulator-init
    load — the gathered (B, d) block never materializes (bit-identical:
    the row adds/epilogue commute with the exact row-copy gather).
    ``interpret=True`` runs the Pallas interpreter (CPU validation).
    """
    d = parts[0][1].shape[1]
    user = [(x, w) for x, w in parts if x.shape[0] == 1]
    rest = [(x, w) for x, w in parts if x.shape[0] != 1]

    # user row computed and kept in f32 — it seeds the f32 accumulator, so
    # rounding it to bf16 here would inject avoidable error (ulp(|u|)).
    u = jnp.zeros((1, d), jnp.float32)
    for x, w in user:
        u = u + x.astype(jnp.float32) @ w.astype(jnp.float32)
    if acc0 is not None:
        # (B, d) acc0 broadcasts u row-wise; a (U, d) table (user_index
        # set) broadcasts identically — per-slot rows, gathered below
        u = u + acc0.astype(jnp.float32)
    if b is not None:
        u = u + b.astype(jnp.float32)

    if not rest:  # no batched stream left: acc-init row/block IS the output
        out = _EPILOGUES[activation](u)
        if user_index is not None and acc0 is not None:
            # clip: a padded row's index must read a real slot, not wrap/NaN
            out = jnp.take(out, user_index, axis=0, mode="clip")
        return out.astype(parts[0][0].dtype)

    B = max(x.shape[0] for x, _ in rest)
    if len(rest) == 1 and rest[0][0].shape[0] == B:
        # single pre-concatenated stream (engine-side weight pre-concat):
        # no per-call operand copies at all
        x_rest, w_rest = rest[0]
    else:
        x_rest = jnp.concatenate(
            [jnp.broadcast_to(x, (B,) + x.shape[1:]) for x, _ in rest], axis=-1)
        w_rest = jnp.concatenate([w for _, w in rest], axis=0)

    Dr = x_rest.shape[1]
    bm, bn, bk = _pick_blocks(B, Dr, d, x_rest.dtype.itemsize)
    Bp, Drp, dp = round_up(B, bm), round_up(Dr, bk), round_up(d, bn)
    xp = jnp.pad(x_rest, ((0, Bp - B), (0, Drp - Dr)))
    wp = jnp.pad(w_rest, ((0, Drp - Dr), (0, dp - d)))
    if user_index is not None and acc0 is not None:
        # table layout (U, d): pad columns only; pad rows index slot 0 and
        # out-of-range indices clamp (same contract as kernels.gather_einsum)
        up = jnp.pad(u, ((0, 0), (0, dp - d)))
        idx = jnp.clip(user_index.astype(jnp.int32), 0, acc0.shape[0] - 1)
        idx = jnp.pad(idx, (0, Bp - B))
        out = mari_matmul_kernel_gather(xp, wp, up, idx, bm=bm, bn=bn,
                                        bk=bk, activation=activation,
                                        interpret=interpret)
        return out[:B, :d]
    # row-wise acc-init pads its batch dim alongside x; a single row does not
    up = jnp.pad(u, ((0, Bp - B if u.shape[0] == B else 0), (0, dp - d)))
    out = mari_matmul_kernel(xp, wp, up, bm=bm, bn=bn, bk=bk,
                             activation=activation, interpret=interpret)
    return out[:B, :d]


@functools.partial(jax.jit, static_argnames=("activation", "interpret"))
def mari_matmul_fused(x_user, x_rest, w_user, w_rest, b=None, *,
                      activation="identity", interpret=False):
    """act(Tile(x_user @ w_user, B) + x_rest @ w_rest (+ b)) — Eq. 7.

    x_user (1, Du), x_rest (B, Dr), w_user (Du, d), w_rest (Dr, d).
    ``interpret=True`` runs the Pallas interpreter (CPU validation).
    """
    return mari_matmul_fused_groups(
        [(x_user, w_user), (x_rest, w_rest)], b,
        activation=activation, interpret=interpret)
