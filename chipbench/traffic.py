"""The general traffic generator: every mix is a JSON file of parameters
(``traffic/<name>.json``) read here, and the same seed gives the same
requests.

Parameters of a mix:

- ``loop``: ``"open"`` (Poisson arrivals at ``rate_per_s``, each request
  timed from its due time) or ``"closed"`` (``clients`` callers, each
  sending its next request when the last one returned);
- ``users``: ``{"kind": "zipf", "s": ..., "universe": ...}`` (user id =
  popularity rank, id 0 the most popular) or ``{"kind": "fresh",
  "universe": ...}`` (every request from a user never seen before);
- ``warm_users``: how many of the most popular users set-up scores before
  the window, coldest first, so the rep cache starts as a long run of the
  same traffic would leave it;
- ``pool``: ``{"min": ..., "max": ...}``, candidates per request, drawn
  log-uniformly;
- ``user_feature_pool`` distinct user feature sets (user id modulo the
  pool) and ``candidate_rows`` rows of candidate features, from which each
  request takes a contiguous slice;
- ``base_seed``: the seed of the multiset of pool sizes, arrival gaps and
  user ranks. A run seed permutes each block of ``BLOCK`` consecutive
  requests within itself and draws its own feature values, so every
  window of a run holds, up to its last part-block, the same requests as
  any other seed's in another order: seeds do the same work.

Features are host numpy arrays, as a feature server would hand them over:
floats are normal with the configuration's ``feature_std``, integer ids
uniform below its ``item_vocab``.
"""
from __future__ import annotations

import itertools
import math
import threading
import time

import numpy as np

FRESH_PRIME = 100_000_007     # user ids (a*i + c) mod p never repeat for i < p
BLOCK = 256                   # requests whose order a run seed permutes


def seed_rngs(seed: int, n: int) -> list[np.random.Generator]:
    """``n`` independent generators from one run seed of any size."""
    ss = np.random.SeedSequence(int(seed) % 2**64)
    return [np.random.default_rng(s) for s in ss.spawn(n)]


def permute_blocks(rng: np.random.Generator, values: np.ndarray,
                   block: int = BLOCK) -> np.ndarray:
    """``values`` with each run of ``block`` consecutive entries permuted
    within itself."""
    out = np.empty_like(values)
    for lo in range(0, len(values), block):
        out[lo:lo + block] = rng.permutation(values[lo:lo + block])
    return out


def zipf_head_share(s: float, universe: int, head: int) -> float:
    """Share of requests whose user is among the ``head`` most popular of a
    bounded Zipf(s) law over ``universe`` users."""
    w = 1.0 / np.arange(1, universe + 1, dtype=np.float64) ** s
    return float(w[:head].sum() / w.sum())


class Traffic:
    """The requests of one run: ``n`` request specs (user id, pool size,
    candidate offset, due time) and the feature arrays they slice."""

    def __init__(self, mix: dict, input_specs: dict, cfg: dict, seed: int,
                 seconds: float):
        self.mix = mix
        fixed = np.random.default_rng(mix["base_seed"])
        order, feats, place = seed_rngs(seed, 3)
        if mix["loop"] == "open":
            n = int(math.ceil(mix["rate_per_s"] * seconds * 1.25)) + 64
        else:
            n = int(mix["requests"])
        self.n = n

        lo, hi = mix["pool"]["min"], mix["pool"]["max"]
        sizes = np.exp(fixed.uniform(np.log(lo), np.log(hi + 1), n))
        self.sizes = permute_blocks(
            order, np.clip(sizes.astype(np.int64), lo, hi))
        users = mix["users"]
        if users["kind"] == "zipf":
            w = 1.0 / np.arange(1, users["universe"] + 1,
                                dtype=np.float64) ** users["s"]
            cdf = np.cumsum(w)
            cdf /= cdf[-1]
            ranks = np.searchsorted(cdf, fixed.random(n), side="left")
            self.uids = permute_blocks(order, ranks).astype(np.int64)
        elif users["kind"] == "fresh":
            if users["universe"] > FRESH_PRIME:
                raise ValueError("fresh users: universe above the prime")
            a, c = (int(x) for x in order.integers(1, FRESH_PRIME - 1, 2))
            self.uids = (a * np.arange(n, dtype=np.int64) + c) % FRESH_PRIME
        else:
            raise ValueError(f"unknown user kind {users['kind']!r}")
        if mix["loop"] == "open":
            gaps = fixed.exponential(1.0 / mix["rate_per_s"], n)
            self.due = np.cumsum(permute_blocks(order, gaps))
        else:
            self.due = None

        rows = int(mix["candidate_rows"])
        self.offsets = place.integers(0, rows - hi + 1, n)
        pool = int(mix["user_feature_pool"])
        self.user_arrays, self.cand_arrays = {}, {}
        for name, (domain, shape, dtype) in input_specs.items():
            lead = pool if domain == "user" else rows
            full = (lead,) + tuple(shape)
            if dtype.startswith("int"):
                arr = feats.integers(0, cfg["item_vocab"], full, dtype=dtype)
            else:
                arr = feats.standard_normal(full, dtype=np.float32)
                arr *= np.float32(cfg["init"]["feature_std"])
            (self.user_arrays if domain == "user"
             else self.cand_arrays)[name] = arr

    def user_feeds(self, uid: int) -> dict:
        slot = int(uid) % next(iter(self.user_arrays.values())).shape[0]
        return {k: v[slot:slot + 1] for k, v in self.user_arrays.items()}

    def cand_feeds(self, i: int) -> dict:
        lo = int(self.offsets[i])
        hi = lo + int(self.sizes[i])
        return {k: v[lo:hi] for k, v in self.cand_arrays.items()}

    def warm_users(self) -> list[int]:
        """User ids set-up scores before the window, coldest first."""
        if self.mix["users"]["kind"] != "zipf":
            return []
        return list(range(int(self.mix.get("warm_users", 0)) - 1, -1, -1))


class Recorder:
    """Per-request times and outcomes, filled from the serving futures."""

    def __init__(self, n: int):
        self.submitted = np.full(n, np.nan)
        self.done = np.full(n, np.nan)
        self.results: dict[int, object] = {}
        self.errors: dict[int, BaseException] = {}
        self._all = threading.Event()
        self._lock = threading.Lock()
        self._pending = 0
        self._closed = False

    def track(self, i: int, fut) -> None:
        with self._lock:
            self._pending += 1
        fut.add_done_callback(lambda f, i=i: self._finish(i, f))

    def _finish(self, i: int, fut) -> None:
        t = time.perf_counter()
        exc = fut.exception()
        if exc is None:
            self.results[i] = fut.result()
        else:
            self.errors[i] = exc
        self.done[i] = t
        with self._lock:
            self._pending -= 1
            if self._closed and self._pending == 0:
                self._all.set()

    def wait_all(self, timeout: float) -> bool:
        """Wait until every tracked request has finished."""
        with self._lock:
            self._closed = True
            if self._pending == 0:
                self._all.set()
        return self._all.wait(timeout)


def open_loop(submit, traffic: Traffic, seconds: float, rec: Recorder
              ) -> tuple[float, int]:
    """Send every request due in ``[0, seconds)`` at its due time. Returns
    (window start, requests sent)."""
    due = traffic.due
    n = int(np.searchsorted(due, seconds, side="left"))
    t0 = time.perf_counter()
    for i in range(n):
        wait = t0 + due[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        rec.submitted[i] = time.perf_counter()
        rec.track(i, submit(i))
    return t0, n


def closed_loop(submit, traffic: Traffic, seconds: float, clients: int,
                rec: Recorder) -> tuple[float, int]:
    """``clients`` callers each send a request, wait for it, and send the
    next until the window closes. Returns (window start, requests sent)."""
    counter = itertools.count()
    t0 = time.perf_counter()
    t_end = t0 + seconds
    sent = [0] * clients

    def client(c: int) -> None:
        while time.perf_counter() < t_end:
            i = next(counter)
            if i >= traffic.n:
                return
            rec.submitted[i] = time.perf_counter()
            fut = submit(i)
            rec.track(i, fut)
            sent[c] += 1
            try:
                fut.result(timeout=max(1.0, t_end + 60 - time.perf_counter()))
            except Exception:       # recorded by the tracker; keep sending
                pass

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return t0, sum(sent)


def readings(rec: Recorder, traffic: Traffic, t0: float, sent: int,
             seconds: float) -> dict:
    """The end-to-end readings of a window that opened at ``t0``:
    ``candidates_per_s`` counts the candidates of every request finished
    by the window's close, over the whole window; an open loop's
    ``p50_ms``/``p95_ms``/``p99_ms`` are taken over every request due in
    the window, each timed from its due time (a stall delays every later
    request), and ``late_ms`` is how late the generator sent each one."""
    done = rec.done[:sent]
    ok = np.zeros(sent, bool)
    ok[[i for i in rec.results if i < sent]] = True
    in_window = ok & (done <= t0 + seconds)
    out = {"ok": ok, "in_window": in_window, "done": done,
           "candidates_per_s":
               float(traffic.sizes[:sent][in_window].sum()) / seconds}
    if traffic.due is not None and ok.any():
        due = t0 + traffic.due[:sent]
        lat = (done[ok] - due[ok]) * 1e3
        for q in (50, 95, 99):
            out[f"p{q}_ms"] = float(np.percentile(lat, q))
        out["late_ms"] = (rec.submitted[:sent] - due) * 1e3
    return out
