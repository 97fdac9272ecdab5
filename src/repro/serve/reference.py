"""Plain float32 reference scores — the yardstick for serving correctness.

The served path rewrites the graph (MaRI), splits it into two stages, and
packs rows into executables whose shapes depend on the batch, the rep
table and the shard count. XLA promises no bit-equality across differently
shaped executables (tiling and reduction order follow the shape), and on a
TPU float32 matmuls at default precision run as bf16 passes. So every
check that crosses shapes compares against ONE plain reference instead:
the un-rewritten graph, single-stage (``vani``), run under
``jax.default_matmul_precision("highest")`` on the request's own feeds.
Bit-equality is kept only where the same executable sees the same shapes.

``SCORE_TOL`` holds the stated tolerance per platform; a score passes when
``|served - ref| <= atol + rtol * |ref|`` (``np.allclose``'s rule).
"""
from __future__ import annotations

import jax
import numpy as np

from repro.graph.executor import Executor
from repro.graph.ir import Graph

SCORE_TOL: dict[str, tuple[float, float]] = {
    # XLA:CPU runs f32 matmuls in f32; differently shaped executables only
    # reassociate reductions (the largest difference seen between them on
    # the test models was 1.8e-7)
    "cpu": (1e-5, 1e-5),
    # TPU v5e: the served path runs f32 matmuls at default precision, i.e.
    # as single bf16 passes. Measured with chip_smoke.py at published
    # widths on one v5e (2048-candidate pools, paper and tpu presets): max
    # |err| 9.2e-3 for paper-ranking (its first layers contract over 4000+
    # features), 4.7e-3 for DIN, at most 0.35x of this bound
    "tpu": (2e-2, 2e-2),
}


def tolerance() -> tuple[float, float]:
    """(atol, rtol) for the current backend."""
    platform = jax.default_backend()
    if platform not in SCORE_TOL:
        raise KeyError(f"no stated score tolerance for platform "
                       f"{platform!r}; known: {sorted(SCORE_TOL)}")
    return SCORE_TOL[platform]


def tol_ratio(scores, ref, tol: tuple[float, float]) -> float:
    """max |scores - ref| / (atol + rtol |ref|): <= 1 means within ``tol``."""
    atol, rtol = tol
    ref = np.asarray(ref, np.float64)
    err = np.abs(np.asarray(scores, np.float64) - ref)
    return float((err / (atol + rtol * np.abs(ref))).max())


class ReferenceScorer:
    """Score requests with the plain float32 reference of ``graph``.

    ``graph``/``params`` are the UN-rewritten model (what a trainer
    produced). Call with a ``ServeRequest``; returns the scores in the
    engine's layout (outputs concatenated on the last axis)."""

    def __init__(self, graph: Graph, params: dict):
        self._run = jax.jit(Executor(graph, "vani").run)
        self._params = params
        self._outputs = list(graph.outputs)

    def __call__(self, req) -> np.ndarray:
        feeds = {**req.user_feeds, **req.candidate_feeds}
        with jax.default_matmul_precision("highest"):
            out = self._run(self._params, feeds)
        return np.concatenate([np.asarray(out[o]) for o in self._outputs],
                              axis=-1)
