"""MaRI matmul as a TPU Pallas kernel.

TPU adaptation of Eq. 7 (DESIGN.md §3): the user-side product
``u = x_user @ w_user`` is a single 1×d row — negligible FLOPs — so the
kernel treats it as a *bias row*: the VMEM accumulator for each output tile
initializes from the broadcast ``u`` tile instead of zeros, and the MXU only
streams the item/cross operand ``x_rest @ w_rest``. ``Tile(u, B)`` never
exists in HBM, and the epilogue add is fused into the matmul.

The epilogue additionally applies the layer's activation in-register
(``activation``), so the (B, d) pre-activation never round-trips through
HBM between the matmul and the nonlinearity.

Grid: (B/bm, d/bn, Dr/bk), k innermost; accumulator in f32 VMEM scratch.
Block shapes are (8,128)-aligned for the MXU systolic array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Epilogue activations computed on the f32 accumulator tile. Kept in sync
# with repro.nn.layers.ACTIVATIONS (not imported to keep the kernel module
# dependency-free).
_EPILOGUES = {
    "identity": lambda x: x,
    "relu": jax.nn.relu,
    "gelu": jax.nn.gelu,
    "silu": jax.nn.silu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
}


def _kernel(x_ref, w_ref, u_ref, o_ref, acc_ref, *, activation):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        # Eq. 7's Tile(x_u W_u, B): broadcast the user row into the tile.
        acc_ref[...] = jnp.broadcast_to(
            u_ref[...].astype(jnp.float32), acc_ref.shape)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = _EPILOGUES[activation](acc_ref[...]).astype(o_ref.dtype)


def _kernel_gather(idx_ref, x_ref, w_ref, u_hbm, o_ref, acc_ref, rows_ref,
                   sem, *, activation):
    """Row-wise variant with the user-rep gather folded into the
    accumulator-init load. ``idx_ref`` is the (B,) user index, prefetched
    to SMEM; ``u_hbm`` the stacked f32 rep table as (U, 1, d), left in HBM
    (the unit middle dim puts the user on an untiled axis, so one user's
    row is a legal DMA slice). At the first k step each row r of the tile
    DMAs its (1, bn) slice of table row ``idx[r]`` into ``rows_ref``, which
    then seeds the accumulator: neither the gathered (B, d) block nor the
    whole table is ever resident, so VMEM use does not grow with U."""
    i, j = pl.program_id(0), pl.program_id(1)
    bm, bn = acc_ref.shape

    @pl.when(pl.program_id(2) == 0)
    def _init():
        def row_copy(r):
            return pltpu.make_async_copy(
                u_hbm.at[pl.ds(idx_ref[i * bm + r], 1), :, pl.ds(j * bn, bn)],
                rows_ref.at[pl.ds(r, 1)], sem)

        def start(r, carry):
            row_copy(r).start()
            return carry

        def wait(r, carry):
            row_copy(r).wait()
            return carry

        jax.lax.fori_loop(0, bm, start, 0)
        jax.lax.fori_loop(0, bm, wait, 0)
        acc_ref[...] = rows_ref[:, 0, :]

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = _EPILOGUES[activation](acc_ref[...]).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "activation", "interpret"))
def mari_matmul_kernel(x_rest, w_rest, u_row, *, bm=128, bn=128, bk=512,
                       activation="identity", interpret=False):
    """act(x_rest (B, Dr) @ w_rest (Dr, d) + u_row).

    ``u_row`` is the accumulator init in one of two layouts:

    * (1, d) — one user per batch (classic Eq. 7): the row is broadcast
      into every output tile.
    * (B, d) — row-wise (cross-user coalesced serving): row b carries user
      b's precomputed partial, so each output tile initializes from its own
      row block. The broadcast in the init is then a no-op.

    Caller guarantees B % bm == 0, d % bn == 0, Dr % bk == 0 (ops.py pads).
    """
    B, Dr = x_rest.shape
    d = w_rest.shape[1]
    assert B % bm == 0 and d % bn == 0 and Dr % bk == 0, (B, Dr, d, bm, bn, bk)
    if u_row.shape[0] not in (1, B):
        raise ValueError(f"u_row rows must be 1 or B={B}, got {u_row.shape}")
    if activation not in _EPILOGUES:
        raise ValueError(f"unsupported epilogue activation {activation!r}")
    if u_row.shape[0] == 1:
        u_spec = pl.BlockSpec((1, bn), lambda i, j, k: (0, j))
    else:                                 # row-wise: follow the output tiling
        u_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))
    return pl.pallas_call(
        functools.partial(_kernel, activation=activation),
        grid=(B // bm, d // bn, Dr // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),   # x tile
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),   # w tile
            u_spec,                                           # acc-init tile
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((B, d), x_rest.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x_rest, w_rest, u_row)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "activation", "interpret"))
def mari_matmul_kernel_gather(x_rest, w_rest, u_table, user_index, *,
                              bm=128, bn=128, bk=512,
                              activation="identity", interpret=False):
    """act(x_rest (B, Dr) @ w_rest (Dr, d) + u_table[user_index]).

    ``u_table`` is the stacked (U, d) per-user accumulator-init table
    (cross-user coalesced serving) and ``user_index`` the (B,) row->user
    map; the gather happens at accumulator-init load inside the kernel,
    so the (B, d) gathered block is never materialized. Bit-identical to
    ``mari_matmul_kernel(x, w, u_table[user_index])`` — a gather is an
    exact row copy and commutes with the elementwise epilogue.

    Caller guarantees B % bm == 0, d % bn == 0, Dr % bk == 0, in-range
    indices and an f32 table (ops.py pads, clamps and casts).
    """
    B, Dr = x_rest.shape
    d = w_rest.shape[1]
    assert B % bm == 0 and d % bn == 0 and Dr % bk == 0, (B, Dr, d, bm, bn, bk)
    if user_index.shape != (B,):
        raise ValueError(f"user_index must be ({B},), got {user_index.shape}")
    if u_table.dtype != jnp.float32:
        raise ValueError(f"u_table must be float32 (it is DMAed into the "
                         f"f32 accumulator), got {u_table.dtype}")
    if activation not in _EPILOGUES:
        raise ValueError(f"unsupported epilogue activation {activation!r}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B // bm, d // bn, Dr // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k, idx: (i, k)),   # x tile
            pl.BlockSpec((bk, bn), lambda i, j, k, idx: (k, j)),   # w tile
            pl.BlockSpec(memory_space=pl.ANY),                     # table
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k, idx: (i, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32),
                        pltpu.VMEM((bm, 1, bn), jnp.float32),
                        pltpu.SemaphoreType.DMA],
    )
    return pl.pallas_call(
        functools.partial(_kernel_gather, activation=activation),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, d), x_rest.dtype),
        interpret=interpret,
    )(user_index.astype(jnp.int32), x_rest, w_rest,
      u_table.reshape(u_table.shape[0], 1, d))
