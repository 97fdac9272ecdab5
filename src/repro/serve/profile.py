"""Stage-boundary timing taxonomy for the serving hot path.

The dispatch-overhead war is fought in microseconds of *host* work per
call, and you cannot win a war you cannot see. ``StageProfiler`` splits a
``score``/``score_coalesced`` call into the phases that matter for a
two-stage ranker:

* ``stage1``   — user-tower compute on cache miss (device, blocking);
* ``pack``     — host-side bucket assembly: transfer-buffer fills, slot
  resolution, device-table row writes;
* ``dispatch`` — enqueueing stage-2 executables (host time only when the
  async-unpack path is active; includes device time on the blocking
  hedged path);
* ``device``   — waiting on stage-2 results (``block_until_ready``);
* ``unpack``   — materializing scores to host and slicing per-request
  views out of the bucket;
* ``queue_idle`` — batcher-worker time blocked on an empty request queue
  with nothing in flight (true starvation, not loop overhead).

Phases are cumulative wall-clock totals plus call counts, cheap enough to
stay on permanently. They are fed by ``repro.obs.trace.span``, which puts
the same spans on the profiler's clock as ``serve.*`` annotations. The
engine threads one profiler through its lifetime;
``RankingService.stats()`` and ``benchmarks/run.py``'s
``serve/<mode>/breakdown`` rows read snapshots.

Thread safety: totals are mutated under a lock because the coalescing
batcher's worker thread and direct ``score`` callers may profile
concurrently against one engine.
"""
from __future__ import annotations

import threading

PHASES = ("stage1", "pack", "dispatch", "device", "unpack", "queue_idle")


class StageProfiler:
    """Cumulative per-phase wall-clock accounting for the serve hot path."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._total_s: dict[str, float] = {p: 0.0 for p in PHASES}
        self._calls: dict[str, int] = {p: 0 for p in PHASES}

    def add(self, name: str, seconds: float) -> None:
        if name not in self._total_s:
            raise KeyError(f"unknown profile phase {name!r}; "
                           f"expected one of {PHASES}")
        with self._lock:
            self._total_s[name] += seconds
            self._calls[name] += 1

    def snapshot(self, reset: bool = False) -> dict[str, dict[str, float]]:
        """Per-phase ``{total_ms, calls, mean_us}`` (zero-safe).

        ``reset=True`` zeroes the totals under the SAME lock acquisition
        — the atomic read-and-clear bench loops need. A separate
        ``snapshot(); reset()`` pair loses every phase event recorded
        between the two calls (the batcher worker profiles concurrently),
        silently shrinking the next window's denominator."""
        with self._lock:
            out = {}
            for p in PHASES:
                calls = self._calls[p]
                total = self._total_s[p]
                out[p] = {
                    "total_ms": total * 1e3,
                    "calls": calls,
                    "mean_us": (total / calls * 1e6) if calls else 0.0,
                }
            if reset:
                for p in PHASES:
                    self._total_s[p] = 0.0
                    self._calls[p] = 0
            return out

    def reset(self) -> None:
        with self._lock:
            for p in PHASES:
                self._total_s[p] = 0.0
                self._calls[p] = 0
