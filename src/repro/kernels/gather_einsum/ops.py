"""Public gather-aware einsum op: clamp the index, pad the row dim to the
tile size, sort rows by user, build the (tile, user) step schedule,
dispatch the Pallas kernel, and restore row order.

``gather_einsum(spec, x, table, user_index)`` computes
``einsum(spec, x, table[user_index])`` for the specs in
``kernel.SPECS`` WITHOUT materializing the gathered ``(B, ...)`` operand
— each kernel step contracts one user's table row against that user's
rows. ``gather_einsum_ref`` (ref.py) is the jnp.take-based oracle and the
executor's non-Pallas fallback.

Index contract (shared with ``mari_matmul``'s kernel-gather path):

* ``user_index`` is ``(B,)`` integer, row ``b`` reads ``table[user_index[b]]``;
* out-of-range values CLAMP to ``[0, U-1]`` — matching the reference's
  ``mode="clip"`` — so a garbage index in a padded row can never wrap to an
  arbitrary user or poison the row with NaN;
* rows added here to pad ``B`` up to the tile size index slot 0; their
  outputs are sliced off before returning.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.common import round_up
from repro.kernels.gather_einsum.kernel import (SPECS, gather_einsum_kernel,
                                                parse_spec)

_BLOCK_B = 256
# per-step VMEM for the double-buffered x / table-row / output blocks;
# below v5e's 16 MiB default scoped limit with room for the index block
_VMEM_BUDGET = 12 * 1024 * 1024


def _vmem_bytes(shape: tuple[int, ...], itemsize: int = 4) -> int:
    """Bytes of one VMEM block: the last two dims pad to (8, 128) tiles."""
    *lead, sub, lane = (1,) * max(0, 2 - len(shape)) + tuple(shape)
    return math.prod(lead) * round_up(sub, 8) * round_up(lane, 128) * itemsize


def _pick_rows(spec: str, B: int, x_shape, t_shape) -> int:
    """Largest row tile (multiple of 8, <= 256) whose double-buffered
    blocks fit the VMEM budget."""
    x_sub, t_sub, _, _ = parse_spec(spec)
    sizes = dict(zip(x_sub, x_shape)) | dict(zip(t_sub, t_shape))
    layout = SPECS[spec][1]
    t_row = (1,) * (len(t_shape) == 2) + (1,) + tuple(t_shape[1:])
    bm = min(_BLOCK_B, round_up(B, 8))
    while bm > 8:
        blocks = ((bm,) + tuple(x_shape[1:]), t_row,
                  tuple(bm if c == "b" else sizes[c] for c in layout))
        if 2 * sum(_vmem_bytes(b) for b in blocks) <= _VMEM_BUDGET:
            break
        bm //= 2
    return bm


@functools.partial(jax.jit, static_argnames=("spec", "interpret"))
def gather_einsum(spec, x, table, user_index, *, interpret=False):
    """``einsum(spec, x, table[user_index])``, gather fused into the kernel.

    ``interpret=True`` runs the Pallas interpreter (CPU validation).
    """
    if spec not in SPECS:
        raise ValueError(f"gather_einsum supports {sorted(SPECS)}, got "
                         f"{spec!r}")
    B, U = x.shape[0], table.shape[0]
    bm = _pick_rows(spec, B, x.shape, table.shape)
    Bp = round_up(B, bm)
    idx = jnp.clip(user_index.astype(jnp.int32), 0, U - 1)
    if Bp != B:
        x = jnp.pad(x, ((0, Bp - B),) + ((0, 0),) * (x.ndim - 1))
        idx = jnp.pad(idx, (0, Bp - B))      # padding rows index slot 0
    order = jnp.argsort(idx, stable=True)
    sidx = idx[order]
    # one step per (row tile, user) pair, in tile order: a pair starts at
    # every tile's first row and wherever the sorted user changes
    row = jnp.arange(Bp)
    starts = (row % bm == 0) | (sidx != jnp.roll(sidx, 1))
    n_steps = Bp // bm + min(U, Bp) - 1
    # padding steps repeat the last pair's blocks (no DMA) and are flagged
    # off, so they compute nothing
    pos = jnp.nonzero(starts, size=n_steps, fill_value=Bp - 1)[0]
    valid = (jnp.arange(n_steps) < starts.sum()).astype(jnp.int32)
    out = gather_einsum_kernel(spec, x[order], table, sidx,
                               (pos // bm).astype(jnp.int32), sidx[pos],
                               valid, bm=bm, interpret=interpret)
    inv = jnp.argsort(order)[:B]             # sorted -> original row order
    if SPECS[spec][1] == "lbh":
        return jnp.take(out, inv, axis=1).transpose(1, 0, 2)
    return jnp.take(out, inv, axis=0)
