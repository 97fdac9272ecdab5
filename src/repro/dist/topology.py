"""Process/device topology for multi-process serving.

``Topology`` describes one worker's place in a (hosts × devices) fleet and
owns the ``jax.distributed`` handshake; ``candidate_mesh`` flattens the
fleet's devices into the 1-D 'cand' mesh stage 2 shards over; the bucket
planner rounds candidate buckets so **no shard ever receives a ragged
tail** — every compiled stage-2 shape divides evenly over the mesh, which
is what keeps the multi-process dispatch collective-free until the final
score all-gather.

Bucket invariants (property-tested in ``tests/test_dist.py``):

* every bucket is a power of two and a multiple of the shard count;
* per-shard work (bucket / shards) is itself a power of two — one compiled
  executable family per (bucket, shard-count), aligned work per device;
* total padding over a pool never exceeds one bucket.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import numpy as np

from repro.common import next_pow2, prev_pow2


# ---------------------------------------------------------------------------
# Process topology
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Topology:
    """One worker's view of the serving fleet.

    ``initialize()`` must run before any other jax call in the process
    (device enumeration locks on first use). Single-process topologies
    skip the distributed handshake entirely — the degenerate case needs
    no coordinator.
    """
    num_processes: int = 1
    process_id: int = 0
    coordinator: str = "localhost:12421"

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1

    def initialize(self) -> "Topology":
        if self.is_distributed:
            # CPU backends cross processes via gloo; TPU backends ignore
            # the setting and use ICI/DCN.
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
            jax.distributed.initialize(
                coordinator_address=self.coordinator,
                num_processes=self.num_processes,
                process_id=self.process_id)
        return self

    @classmethod
    def from_env(cls) -> "Topology":
        """Read REPRO_NUM_PROCESSES / REPRO_PROCESS_ID / REPRO_COORDINATOR
        (the runner CLI sets them for its spawned workers)."""
        return cls(
            num_processes=int(os.environ.get("REPRO_NUM_PROCESSES", "1")),
            process_id=int(os.environ.get("REPRO_PROCESS_ID", "0")),
            coordinator=os.environ.get("REPRO_COORDINATOR",
                                       "localhost:12421"))


def candidate_mesh(n_shards: int | None = None) -> jax.sharding.Mesh:
    """1-D 'cand' mesh over the largest power-of-two prefix of the global
    device list (all processes' devices after ``Topology.initialize``).
    ``n_shards`` clamps the shard count (must be a power of two)."""
    devs = jax.devices()
    n = prev_pow2(len(devs))
    if n_shards is not None:
        if n_shards & (n_shards - 1):
            raise ValueError(f"n_shards must be a power of two: {n_shards}")
        n = min(n, n_shards)
    return jax.sharding.Mesh(np.asarray(devs[:n]), ("cand",))


# ---------------------------------------------------------------------------
# Collective-aware bucket planner
# ---------------------------------------------------------------------------

def bucket_for(n: int, shards: int, *, min_bucket: int = 128,
               max_batch: int = 4096) -> int:
    """Smallest valid bucket holding ``n`` rows: a power of two, at least
    ``max(min_bucket, shards)``, at most ``max_batch`` — so bucket % shards
    == 0 and per-shard work is a power of two.

    With ``shards > 1`` a non-power-of-two ``max_batch`` cap is rounded
    DOWN to the nearest power of two (never below ``shards``): a cap-sized
    bucket must itself divide evenly over the mesh. Unsharded callers keep
    the raw cap (seed behavior — a cap-sized bucket needs no alignment).
    """
    if shards & (shards - 1):
        raise ValueError(f"shard count must be a power of two: {shards}")
    hi = max_batch if shards == 1 else max(prev_pow2(max_batch), shards)
    lo = max(min(min_bucket, hi), shards)
    return min(hi, next_pow2(max(n, lo)))


def plan_buckets(pool: int, shards: int, *, min_bucket: int = 128,
                 max_batch: int = 4096) -> list[int]:
    """Decompose a candidate pool into shard-aligned buckets.

    Greedy: full ``max_batch`` buckets while the remainder overflows one,
    then a single tail bucket sized to the remainder — so total padding is
    strictly less than the (smallest) tail bucket, i.e. never exceeds one
    bucket, and every bucket divides evenly over ``shards``.
    """
    if pool <= 0:
        return []
    out: list[int] = []
    rem = pool
    while rem > 0:
        b = bucket_for(rem, shards, min_bucket=min_bucket,
                       max_batch=max_batch)
        out.append(b)
        rem -= b
    return out
