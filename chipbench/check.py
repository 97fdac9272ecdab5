"""The comparison that decides ``correct``: served scores of requests
finished in the window against the plain float32 reference.

A sample is drawn from the seed among the requests the window finished:
half from those whose user's reps came from the rep cache (hits) and half
from those that ran stage 1 (misses), as far as each kind exists, plus the
request with the largest pool. The number compared is the widest gap
between a served score and the reference's score of the same candidate;
a run is correct when it is within the configuration's limit and no
request failed.

The control goes through the same comparison: the reference at a lower
precision is put in the program's place (``stand_in``), its answers are
compared with the float32 reference, and ``verdict`` has to say not
correct.
"""
from __future__ import annotations

import dataclasses

import numpy as np

SAMPLE = 16


def pick_sample(results: dict, sizes: np.ndarray, rng: np.random.Generator,
                n: int = SAMPLE) -> list[int]:
    """Request indices to compare, from ``results`` {index: ServeResult}."""
    done = sorted(results)
    if not done:
        return []
    hits = [i for i in done if results[i].user_cache_hit]
    misses = [i for i in done if not results[i].user_cache_hit]
    take_h = min(len(hits), max(n // 2, n - len(misses)))
    take_m = min(len(misses), n - take_h)
    pick = set(rng.choice(hits, take_h, replace=False).tolist()
               if take_h else [])
    pick |= set(rng.choice(misses, take_m, replace=False).tolist()
                if take_m else [])
    pick.add(max(done, key=lambda i: (int(sizes[i]), -i)))
    return sorted(pick)


def compare(sample: list[int], results: dict, traffic, reference) -> dict:
    """Widest |served - reference| over every score of the sampled
    requests, with what the sample covered."""
    worst = 0.0
    n_scores = 0
    for i in sample:
        served = np.asarray(results[i].scores, np.float64)
        uid = int(traffic.uids[i])
        ref = np.asarray(reference(traffic.user_feeds(uid),
                                   traffic.cand_feeds(i)), np.float64)
        if served.shape != ref.shape or not np.isfinite(served).all():
            return {"max_abs_err": float("inf"), "requests": len(sample),
                    "bad_shape_or_nonfinite": i}
        worst = max(worst, float(np.abs(served - ref).max()))
        n_scores += served.size
    return {"max_abs_err": worst, "requests": len(sample),
            "scores": n_scores,
            "hits": sum(bool(results[i].user_cache_hit) for i in sample),
            "coalesced": sum(bool(results[i].coalesced) for i in sample)}


def verdict(reading: dict, failed: int, limit: float) -> bool:
    """``correct``: something was compared, the widest gap is within the
    limit, and no request failed."""
    return (reading.get("requests", 0) > 0
            and reading["max_abs_err"] <= limit and failed == 0)


@dataclasses.dataclass
class StandIn:
    """An answer made by a stand-in for the program, shaped as the served
    result the comparison reads."""
    scores: np.ndarray
    user_cache_hit: bool
    coalesced: bool


def stand_in(sample: list[int], results: dict, traffic, reference) -> dict:
    """{index: answer} of ``reference`` put in the program's place for the
    sampled requests, with the served requests' cache and coalescing
    flags."""
    out = {}
    for i in sample:
        uid = int(traffic.uids[i])
        out[i] = StandIn(np.asarray(reference(traffic.user_feeds(uid),
                                              traffic.cand_feeds(i))),
                         bool(results[i].user_cache_hit),
                         bool(results[i].coalesced))
    return out
