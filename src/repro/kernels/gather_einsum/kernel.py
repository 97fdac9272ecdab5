"""Gather-aware einsum as a TPU Pallas kernel.

Cross-user coalesced serving hands stage 2 a stacked ``(U, ...)`` user-rep
table plus a per-row ``user_index``; the materializing path gathers the
table to ``(B, ...)`` before every contraction, which at coalesced batch
sizes re-creates exactly the HBM traffic MaRI's one-shot tensors were
built to avoid (for reparam DIN the gathered ``T`` block is ``(B, L, D, h)``).
This kernel family folds the gather into the contraction: each grid step
loads ONE user's table row into VMEM and contracts it against that user's
rows, so the gathered ``(B, ...)`` operand never exists in HBM.

Supported specs are the decomposed-attention contractions — the first
operand is per-row (leading ``b``), the second is the stacked table
(leading ``u``), and the output is per-row:

* ``"bd,uldh->blh"`` — q against the one-shot tensor ``T``;
* ``"bl,uld->bd"``   — attention weights against the boundary keys;
* ``"blh,uh->bl"``   — per-row contraction against a per-user vector table.

Layout: the caller (ops.py) sorts rows by user, so each user's rows form
one run and a row tile of ``bm`` rows meets only the users whose runs
cross it. The grid is 1-D over (row tile, user) pairs — at most
``B/bm + min(U, B) - 1`` steps — whose tile and user ids are
scalar-prefetched to SMEM and drive the ``index_map``s. Per step the kernel
holds the x tile, ONE user's ``(1, ...)`` table row and the tile's sorted
indices; consecutive steps with the same tile or the same user reuse the
resident block, so each user's row crosses HBM about once and VMEM use
does not grow with U. A step writes only the rows whose index equals its
user (the output tile stays resident across the steps that share it);
padding steps beyond the real pair count are flagged off and do nothing.
Row results depend only on ``x[b]`` and ``table[idx[b]]``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def parse_spec(spec: str) -> tuple[str, str, str, str]:
    """Validate a gather-einsum spec; returns (x_sub, t_sub, out_sub,
    row_spec) where ``row_spec`` is the per-row einsum after the gather
    (``u`` replaced by ``b``)."""
    try:
        lhs, out = spec.split("->")
        x_sub, t_sub = lhs.split(",")
    except ValueError:
        raise ValueError(f"gather_einsum spec must be 'b...,u...->b...', "
                         f"got {spec!r}") from None
    if not (x_sub.startswith("b") and t_sub.startswith("u")
            and out.startswith("b")):
        raise ValueError(
            f"gather_einsum spec {spec!r}: first operand must lead with the "
            f"row dim 'b', the table with the user dim 'u', the output with "
            f"'b'")
    if "u" in x_sub or "u" in out or "b" in t_sub:
        raise ValueError(f"gather_einsum spec {spec!r}: 'u' lives only on "
                         f"the table operand, 'b' never does")
    for sub in (x_sub, t_sub, out):
        if len(set(sub)) != len(sub):
            raise ValueError(f"gather_einsum spec {spec!r}: repeated dim "
                             f"in {sub!r}")
    if not set(out[1:]) <= set(x_sub[1:]) | set(t_sub[1:]):
        raise ValueError(f"gather_einsum spec {spec!r}: output dim not "
                         f"present in any operand")
    return x_sub, t_sub, out, f"{x_sub},b{t_sub[1:]}->{out}"


# Per-spec row bodies: (x tile, ONE user's table row, (bm, 1) row mask,
# output block ref). Each writes the masked rows of its output block.

def _q_against_t(x_ref, t_ref, mask, o_ref):
    # "bd,uldh->blh" as L small (bm, D) @ (D, h) matmuls; the output block
    # is (L, bm, h) so each l writes a whole leading-dim slab (ops.py
    # transposes back to (B, L, h))
    x = x_ref[...]

    def step(l, carry):
        y = jnp.dot(x, t_ref[0, l], preferred_element_type=jnp.float32)
        o_ref[l] = jnp.where(mask, y, o_ref[l]).astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, t_ref.shape[1], step, 0)


def _weights_against_keys(x_ref, t_ref, mask, o_ref):
    # "bl,uld->bd": one (bm, L) @ (L, D) matmul
    y = jnp.dot(x_ref[...], t_ref[0], preferred_element_type=jnp.float32)
    o_ref[...] = jnp.where(mask, y, o_ref[...]).astype(o_ref.dtype)


def _rows_against_vector(x_ref, t_ref, mask, o_ref):
    # "blh,uh->bl": the table arrives as (U, 1, h) — a lane-dense row
    y = jnp.sum(x_ref[...] * t_ref[...], axis=-1)
    o_ref[...] = jnp.where(mask, y, o_ref[...]).astype(o_ref.dtype)


# spec -> (row body, output layout of the kernel: "blh" is written as
# (L, B, h) and transposed by the caller)
SPECS = {
    "bd,uldh->blh": (_q_against_t, "lbh"),
    "bl,uld->bd": (_weights_against_keys, "bd"),
    "blh,uh->bl": (_rows_against_vector, "bl"),
}


def _kernel(tile_ref, user_ref, valid_ref, x_ref, t_ref, idx_ref, o_ref, *,
            body):
    s = pl.program_id(0)

    @pl.when(valid_ref[s] == 1)
    def _step():
        body(x_ref, t_ref, idx_ref[...] == user_ref[s], o_ref)


@functools.partial(jax.jit, static_argnames=("spec", "bm", "interpret"))
def gather_einsum_kernel(spec, x, table, sorted_index, step_tile, step_user,
                         step_valid, *, bm, interpret=False):
    """``einsum(spec, x, table[sorted_index])`` over rows sorted by user.

    ``x`` is ``(B, ...)`` with rows sorted so equal indices are contiguous,
    ``table`` the stacked ``(U, ...)`` rep table, ``sorted_index`` the
    ``(B,)`` int32 row->user map in that order, and ``step_*`` the
    ``(S,)`` int32 (row tile, user, valid) pair schedule. Caller guarantees
    ``B % bm == 0``, in-range indices and a schedule that covers every
    (tile, user) pair in tile order, and a spec in ``SPECS`` (ops.py
    checks and builds all of it). Returns the kernel's output layout
    (``SPECS``), in sorted row order.
    """
    x_sub, t_sub, _, _ = parse_spec(spec)
    if x.ndim != len(x_sub) or table.ndim != len(t_sub):
        raise ValueError(f"gather_einsum {spec!r}: operand ranks "
                         f"{x.shape}/{table.shape} do not match the spec")
    B = x.shape[0]
    if sorted_index.shape != (B,):
        raise ValueError(f"user_index must be ({B},), got "
                         f"{sorted_index.shape}")
    assert B % bm == 0, (B, bm)
    sizes = {c: s for c, s in zip(x_sub, x.shape)}
    for c, s in zip(t_sub, table.shape):
        if sizes.setdefault(c, s) != s:
            raise ValueError(f"gather_einsum {spec!r}: dim {c!r} is "
                             f"{sizes[c]} on x but {s} on the table")
    body, layout = SPECS[spec]
    if table.ndim == 2:            # (U, h) -> (U, 1, h): lane-dense rows
        table = table.reshape(table.shape[0], 1, table.shape[1])
    out_shape = tuple(sizes[c] for c in layout)
    b_pos = layout.index("b")
    out_block = tuple(bm if c == "b" else sizes[c] for c in layout)
    zeros = lambda n: (0,) * n
    t_tail = table.shape[1:]

    def out_map(s, tile, user, valid):
        return tuple(tile[s] if i == b_pos else 0 for i in range(len(layout)))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(step_tile.shape[0],),
        in_specs=[
            pl.BlockSpec((bm,) + x.shape[1:],
                         lambda s, tile, user, valid:
                         (tile[s],) + zeros(x.ndim - 1)),          # x tile
            pl.BlockSpec((1,) + t_tail,
                         lambda s, tile, user, valid:
                         (user[s],) + zeros(len(t_tail))),         # one user
            pl.BlockSpec((bm, 1),
                         lambda s, tile, user, valid: (tile[s], 0)),  # idx
        ],
        out_specs=pl.BlockSpec(out_block, out_map),
    )
    return pl.pallas_call(
        functools.partial(_kernel, body=body),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        # the output tile is revisited by consecutive steps: sequential grid
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(step_tile, step_user, step_valid, x, table,
      sorted_index.reshape(B, 1))
