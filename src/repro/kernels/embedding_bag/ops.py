"""Public EmbeddingBag op: sorts by segment, runs the Pallas kernel, zeroes
empty segments, applies the combiner."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.embedding_bag.kernel import embedding_bag_kernel


@functools.partial(jax.jit,
                   static_argnames=("num_segments", "combiner", "interpret"))
def embedding_bag(table, ids, segment_ids, *, num_segments: int,
                  combiner: str = "sum", interpret: bool = False):
    """Pooled multi-hot lookup: out[s] = pool_{i: seg[i]==s} table[ids[i]]."""
    order = jnp.argsort(segment_ids)
    ids_s = ids[order]
    seg_s = segment_ids[order]
    out = embedding_bag_kernel(table, ids_s, seg_s,
                               num_segments=num_segments, interpret=interpret)
    counts = jax.ops.segment_sum(jnp.ones_like(ids, jnp.float32), segment_ids,
                                 num_segments=num_segments)
    out = jnp.where((counts > 0)[:, None], out, 0)
    if combiner == "mean":
        out = out / jnp.maximum(counts, 1.0)[:, None]
    return out
