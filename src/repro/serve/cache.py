"""Bounded user-representation store for the serving runtime.

Stage-1 outputs (user activations + per-``mari_dense`` partials +
decomposed-attention one-shot tensors) are cached per
``(user_id, feature_version)`` so repeat users skip the user tower. The
seed engine kept these in an unbounded dict — at "millions of users" scale
that is an OOM, not a cache. ``UserRepCache`` is the replacement:

* **LRU bound** — ``max_users`` caps live entries; inserting past the cap
  evicts the least-recently-*scored* user and bumps ``evictions`` (surfaced
  on the engine for capacity monitoring).
* **version supersede** — one live entry per user: putting a new
  ``feature_version`` frees every older version of that user immediately
  (feature updates must not accumulate stale representations).
* **invalidation** — ``invalidate_user`` drops all versions of a user
  (logout, feature backfill, GDPR delete).
* **thread safety** — the async batcher's worker thread and callers of
  ``ServingEngine.score`` touch the cache concurrently; every mutation is
  taken under one lock.
* **removal listeners** — ``subscribe`` registers callbacks fired whenever
  a user's entry leaves the cache for ANY reason (LRU eviction, version
  supersede, invalidation, clear). The device tier below uses this to
  recycle its slots in lockstep with the host tier;
  ``subscribe_removal`` delivers the full removal record
  ``(user_id, version, reps, reason)`` — the cold tier (``repro.mem``)
  uses it to demote evicted reps instead of discarding them. Listener
  snapshots are taken under the SAME lock acquisition as the mutation
  and callbacks fire strictly after release: listeners are free to take
  their own locks (the cold-tier arena lock, the device-store lock)
  without any lock-order inversion against the cache lock.

``DeviceRepStore`` is the *device tier*: instead of re-stacking cached
per-user rows into a fresh ``(U, ...)`` table on every bucket dispatch
(a ``jnp.concatenate`` per boundary per call — the dominant host cost the
benchmarks exposed), it holds ONE persistent stacked ``(capacity, ...)``
jax array per boundary and writes a single row per new user via a donated
``.at[slot].set`` update. Stage 2 then consumes the persistent tables with
per-row *slot indices*; freeing a user merely recycles its slot integer —
the stale row stays in the table but is never referenced, and the
engine's ``mode="clip"`` gathers make even an out-of-range index safe.
"""
from __future__ import annotations

import threading
from collections import OrderedDict
from typing import Any, Callable, Hashable, Mapping, Sequence

Key = tuple[Hashable, Hashable]          # (user_id, feature_version)

# removal reasons delivered to subscribe_removal listeners
EVICT = "evict"            # LRU-bound eviction (reps still valid: demotable)
SUPERSEDE = "supersede"    # newer feature_version replaced the entry
INVALIDATE = "invalidate"  # explicit invalidate_user (GDPR/logout/backfill)
CLEAR = "clear"            # cache.clear()

# one removal: (user_id, feature_version, reps, reason)
Removal = tuple[Hashable, Hashable, Mapping[str, Any], str]


def _reps_nbytes(reps: Mapping[str, Any]) -> dict[str, int]:
    """Per-boundary byte sizes of one user's rep pytree (best effort)."""
    out = {}
    for k, v in reps.items():
        out[k] = int(getattr(v, "nbytes", 0))
    return out


class UserRepCache:
    """LRU mapping (user_id, feature_version) -> stage-1 output pytree.

    Stored keyed by user_id with the live version alongside, so the
    one-live-entry-per-user invariant costs O(1) per insert — a key scan
    per put would be O(cache size) and melt under miss traffic at the
    intended scale.
    """

    def __init__(self, max_users: int | None = None):
        if max_users is not None and max_users < 1:
            raise ValueError(f"max_users must be >= 1, got {max_users}")
        self.max_users = max_users
        # user_id -> (feature_version, reps); insertion order == LRU order
        self._entries: OrderedDict[
            Hashable, tuple[Hashable, Mapping[str, Any]]] = OrderedDict()
        self._lock = threading.Lock()
        self.evictions = 0               # LRU-bound evictions only
        self.hits = 0
        self.misses = 0
        self._listeners: list[Callable[[Hashable], None]] = []
        self._removal_listeners: list[Callable[..., None]] = []
        self._tracer = None              # repro.obs.Tracer, when tracing

    def set_tracer(self, tracer) -> None:
        """Attach a ``repro.obs.Tracer``: every removal (eviction,
        supersede, invalidate, clear) emits a ``cache_evict`` instant.
        The tracer's lock is a leaf, so emitting is deadlock-free from
        any caller."""
        self._tracer = tracer

    def subscribe(self, on_remove: Callable[[Hashable], None]) -> None:
        """Register a callback fired with ``user_id`` whenever that user's
        entry leaves the cache (eviction, supersede, invalidate, clear).
        Callbacks run outside the cache lock (snapshot taken inside the
        mutating acquisition, fired after release), so they may take
        their own locks. Registration takes the cache lock: with a
        shared cache, one scenario may subscribe while another is
        serving (and notifying)."""
        with self._lock:
            self._listeners.append(on_remove)

    def subscribe_removal(self, on_remove: Callable[..., None]) -> None:
        """Like ``subscribe`` but the callback receives the FULL removal
        record ``(user_id, feature_version, reps, reason)`` with reason
        one of ``evict`` / ``supersede`` / ``invalidate`` / ``clear``.
        Only ``evict`` removals carry reps that are still the live value
        for their key — the cold tier demotes those; the other reasons
        mean the reps are stale and must not be re-served."""
        with self._lock:
            self._removal_listeners.append(on_remove)

    def _snapshot_listeners(self) -> tuple[tuple, tuple]:
        """Caller must hold ``_lock`` — the one mutating acquisition."""
        return tuple(self._listeners), tuple(self._removal_listeners)

    def _fire(self, removed: Sequence[Removal],
              listeners: tuple, removal_listeners: tuple) -> None:
        """Deliver removal callbacks strictly OUTSIDE the cache lock, on
        the snapshots taken inside the mutating acquisition (no second
        acquisition — rules out lock-order inversion against listener
        locks such as the cold-tier arena lock)."""
        if not removed:
            return
        trc = self._tracer
        for uid, ver, reps, reason in removed:
            if trc is not None:
                trc.instant("cache_evict", user=uid, reason=reason)
            for cb in listeners:
                cb(uid)
            for cb in removal_listeners:
                cb(uid, ver, reps, reason)

    def get(self, key: Key) -> Mapping[str, Any] | None:
        user_id, version = key
        with self._lock:
            entry = self._entries.get(user_id)
            if entry is None or entry[0] != version:
                self.misses += 1
                return None
            self._entries.move_to_end(user_id)
            self.hits += 1
            return entry[1]

    def put(self, key: Key, reps: Mapping[str, Any]) -> None:
        user_id, version = key
        removed: list[Removal] = []
        with self._lock:
            # one live entry per user: a newer feature_version overwrites
            # (and frees) the old reps rather than accumulating beside them
            prev = self._entries.get(user_id)
            if prev is not None and prev[0] != version:
                removed.append((user_id, prev[0], prev[1], SUPERSEDE))
            self._entries[user_id] = (version, reps)
            self._entries.move_to_end(user_id)
            while self.max_users is not None and len(self._entries) > self.max_users:
                evicted, (ever, ereps) = self._entries.popitem(last=False)
                self.evictions += 1
                removed.append((evicted, ever, ereps, EVICT))
            listeners, removal_listeners = self._snapshot_listeners()
        self._fire(removed, listeners, removal_listeners)

    def invalidate_user(self, user_id: Hashable) -> int:
        """Drop the cached entry of ``user_id``; returns entries removed."""
        removed: list[Removal] = []
        with self._lock:
            entry = self._entries.pop(user_id, None)
            if entry is not None:
                removed.append((user_id, entry[0], entry[1], INVALIDATE))
            listeners, removal_listeners = self._snapshot_listeners()
        self._fire(removed, listeners, removal_listeners)
        return len(removed)

    def clear(self) -> None:
        with self._lock:
            removed = [(uid, ver, reps, CLEAR)
                       for uid, (ver, reps) in self._entries.items()]
            self._entries.clear()
            listeners, removal_listeners = self._snapshot_listeners()
        self._fire(removed, listeners, removal_listeners)

    def stats(self) -> dict:
        """Occupancy + byte accounting of the host tier.

        ``bytes`` is the total live-rep footprint; ``boundary_bytes`` maps
        each boundary tensor name to its summed bytes across users — the
        number to look at when sizing ``CachePlan.device_slots`` (the
        device tier costs ``capacity * bytes_per_user`` up front).
        """
        with self._lock:
            boundary: dict[str, int] = {}
            for _ver, reps in self._entries.values():
                for k, n in _reps_nbytes(reps).items():
                    boundary[k] = boundary.get(k, 0) + n
            return {
                "users": len(self._entries),
                "max_users": self.max_users,
                "hits": self.hits,
                "misses": self.misses,
                "evictions": self.evictions,
                "bytes": sum(boundary.values()),
                "boundary_bytes": boundary,
            }

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def __contains__(self, key: Key) -> bool:
        user_id, version = key
        with self._lock:
            entry = self._entries.get(user_id)
            return entry is not None and entry[0] == version

    def keys(self) -> list[Key]:
        with self._lock:
            return [(uid, ver) for uid, (ver, _) in self._entries.items()]


class DeviceRepStore:
    """Slot-allocated persistent device tables for stage-1 reps.

    One stacked ``(capacity, ...)`` jax array per boundary tensor, lazily
    allocated from the first user row (shapes validated against
    ``boundary_specs`` when provided). ``ensure_rows`` maps
    ``(user, version)`` keys to slot indices, writing at most one row per
    new user via a jitted donated updater — the table buffer is reused in
    place, so steady-state serving allocates nothing.

    Slot lifecycle: ``drop`` (wired to ``UserRepCache.subscribe``) returns
    a user's slot to the free list without touching table contents; the
    dead row is simply unreferenced until a later user recycles the slot.
    When every slot is pinned by the current bucket (``protect``) and none
    is free, ``ensure_rows`` yields ``None`` for the overflow users and the
    engine falls back to the re-stacking path for that pack.

    NOT thread-safe against concurrent *dispatch*: callers must finish all
    ``ensure_rows`` writes for a batch before launching executables that
    read the tables (the donated writer deletes the previous table buffer).
    ``ServingEngine`` serializes exactly this way. To write while OLDER
    launches are still executing, arm ``fork_next_write`` first — the
    write then copies the table into a fresh generation instead of
    donating, leaving the in-flight buffer intact.
    """

    def __init__(self, capacity: int,
                 boundary_specs: Mapping[str, tuple[int, ...]] | None = None,
                 shardings: Mapping[str, Any] | None = None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._specs = dict(boundary_specs) if boundary_specs else None
        self._shardings = dict(shardings) if shardings else None
        self._tables: dict[str, Any] | None = None
        self._writer = None
        self._writer_cow = None
        self._fork_pending = False
        # user -> (version, slot); insertion order == LRU order
        self._map: OrderedDict[Hashable, tuple[Hashable, int]] = OrderedDict()
        self._free: list[int] = list(range(capacity - 1, -1, -1))
        self._lock = threading.Lock()
        self.writes = 0      # row writes (new user or version supersede)
        self.hits = 0        # ensure_rows served from a live slot
        self.recycles = 0    # LRU slot steals (capacity pressure)
        self.drops = 0       # slots returned via drop()
        self.overflows = 0   # ensure_rows rows that could not get a slot
        self.forks = 0       # copy-on-write generation forks (writes armed
        #                      by fork_next_write under in-flight launches)
        self.quarantines = 0  # generation invalidations (failed write/fork)
        self._tracer = None  # repro.obs.Tracer, when tracing
        self._injector = None  # repro.ft.FaultInjector, when injecting

    def set_tracer(self, tracer) -> None:
        """Attach a ``repro.obs.Tracer`` for slot-lifecycle instants
        (``slot_steal`` / ``table_fork`` / ``slot_drop``). Emitted under
        the store lock — the tracer's lock is a leaf, so that is safe."""
        self._tracer = tracer

    def set_fault_injector(self, injector) -> None:
        """Attach a ``repro.ft.FaultInjector``: row writes poke the
        ``slot_write`` site (plus ``table_fork`` when a copy-on-write
        fork is armed). An injected error rides the existing failed-write
        path (the claimed slot is returned, the exception propagates to
        the engine, which quarantines the generation); the ``corrupt``
        sentinel NaN-poisons the written row so detection happens at
        collect, never at serve."""
        self._injector = injector

    # -- allocation ---------------------------------------------------------
    def _alloc(self, row: Mapping[str, Any]) -> None:
        import jax
        import jax.numpy as jnp

        tables = {}
        for k, v in row.items():
            per_row = tuple(v.shape[1:])
            if self._specs is not None:
                spec = self._specs.get(k)
                if spec is not None and per_row != tuple(spec):
                    raise ValueError(
                        f"boundary {k!r}: rep row shape {per_row} does not "
                        f"match the split's boundary spec {tuple(spec)}")
            tables[k] = jnp.zeros((self.capacity,) + per_row, dtype=v.dtype)
        if self._shardings is not None:
            tables = {k: jax.device_put(t, self._shardings[k])
                      if k in self._shardings else t
                      for k, t in tables.items()}

        def serve_rep_write(tabs, reps, slot):
            return {k: tabs[k].at[slot].set(reps[k][0]) for k in tabs}

        kwargs = {}
        if self._shardings is not None:
            kwargs["out_shardings"] = {
                k: self._shardings.get(k) for k in tables}
        # donate_argnums=0: the previous table generation is consumed in
        # place — a row write costs one row's bandwidth, not a table copy
        self._writer = jax.jit(serve_rep_write, donate_argnums=0, **kwargs)
        # the same update WITHOUT donation: builds a fresh generation and
        # leaves the previous buffer alive for in-flight executables still
        # reading it (see fork_next_write)
        self._writer_cow = jax.jit(serve_rep_write, **kwargs)
        self._tables = tables

    # -- slot resolution ----------------------------------------------------
    def ensure_rows(self, items: Sequence[tuple[Hashable, Hashable,
                                                Mapping[str, Any]]],
                    protect: Sequence[Hashable] = ()) -> list[int | None]:
        """Resolve ``(user, version, reps)`` triples to device slots.

        Live ``(user, version)`` entries are LRU-bumped and reused without
        a write; new users take a free slot (or steal the LRU slot not in
        ``protect``) and get exactly one donated row write. Returns one
        slot per item, ``None`` where capacity ran out.

        MUST complete before any executable that reads ``tables`` is
        launched for this batch — see the class docstring.
        """
        import numpy as np
        protected = set(protect)
        slots: list[int | None] = []
        with self._lock:
            for user, version, reps in items:
                entry = self._map.get(user)
                if entry is not None and entry[0] == version:
                    self._map.move_to_end(user)
                    self.hits += 1
                    slots.append(entry[1])
                    continue
                if entry is not None:
                    # version supersede: rewrite the user's own slot
                    slot = entry[1]
                elif self._free:
                    slot = self._free.pop()
                else:
                    slot = self._steal_lru(protected)
                    if slot is None:
                        self.overflows += 1
                        slots.append(None)
                        continue
                try:
                    if self._injector is not None:
                        act = self._injector.poke("slot_write", user=user,
                                                  slot=slot)
                        if self._fork_pending:
                            act = (self._injector.poke("table_fork",
                                                       user=user, slot=slot)
                                   or act)
                        if act == "corrupt":
                            # detectable corruption: NaN-poison the row
                            # being written — it propagates to any score
                            # gathered from this slot and is caught at
                            # collect (clean reps stay in the host LRU,
                            # so the post-quarantine rebuild is clean)
                            reps = {k: np.full_like(np.asarray(v), np.nan)
                                    if np.issubdtype(np.asarray(v).dtype,
                                                     np.floating)
                                    else v for k, v in reps.items()}
                    if self._tables is None:
                        self._alloc(reps)
                    if self._fork_pending:
                        # copy-on-write: in-flight executables keep the
                        # generation they were handed; writes after this
                        # one donate the (not-yet-published) fork in place
                        self._tables = self._writer_cow(
                            self._tables, dict(reps), np.int32(slot))
                        self._fork_pending = False
                        self.forks += 1
                        if self._tracer is not None:
                            self._tracer.instant("table_fork", user=user,
                                                 slot=slot)
                    else:
                        self._tables = self._writer(self._tables,
                                                    dict(reps),
                                                    np.int32(slot))
                except Exception:
                    # a failed alloc/write (e.g. a rep row violating the
                    # boundary spec) must not leak the slot it claimed; a
                    # version supersede keeps its old entry (the previous
                    # row is still intact — the writer is all-or-nothing)
                    if entry is None:
                        self._free.append(slot)
                    raise
                self.writes += 1
                self._map[user] = (version, slot)
                self._map.move_to_end(user)
                protected.add(user)
                slots.append(slot)
        return slots

    def _steal_lru(self, protected: set) -> int | None:
        for user in self._map:          # iterates LRU -> MRU
            if user not in protected:
                _, slot = self._map.pop(user)
                self.recycles += 1
                if self._tracer is not None:
                    self._tracer.instant("slot_steal", user=user, slot=slot)
                return slot
        return None

    # -- lifecycle ----------------------------------------------------------
    def drop(self, user: Hashable) -> None:
        """Recycle ``user``'s slot (cache eviction/invalidation hook).
        The table row is left as-is: dead slots are never referenced, and
        stage-2 gathers clamp, so no zeroing pass is needed."""
        with self._lock:
            entry = self._map.pop(user, None)
            if entry is not None:
                self._free.append(entry[1])
                self.drops += 1
                if self._tracer is not None:
                    self._tracer.instant("slot_drop", user=user,
                                         slot=entry[1])

    def slot_of(self, user: Hashable) -> int | None:
        with self._lock:
            entry = self._map.get(user)
            return None if entry is None else entry[1]

    def quarantine(self, reason: str = "") -> None:
        """Invalidate the current table generation wholesale.

        A failed donated write or fork leaves no guarantee about the
        generation's contents (the writer may have consumed the previous
        buffer before failing), so nothing in it may ever be served
        again: the slot map clears, every slot returns to the free list,
        and the tables drop — they rebuild lazily from the host LRU on
        the next ``ensure_rows`` (one row write per user, exactly like a
        cold start). The host tier is untouched: quarantine costs
        re-WRITES, never re-COMPUTES. Any in-flight executable keeps the
        generation it was handed alive via its own reference, so this is
        safe under the continuous loop."""
        with self._lock:
            self._map.clear()
            self._free = list(range(self.capacity - 1, -1, -1))
            self._tables = None
            self._fork_pending = False
            self.quarantines += 1
            if self._tracer is not None:
                self._tracer.instant("quarantine", reason=reason[:120])

    def fork_next_write(self) -> None:
        """Arm copy-on-write for the NEXT row write: instead of donating
        the current table generation in place, it builds a fresh one and
        leaves the old buffer intact. The continuous dispatch loop arms
        this when launches are still in flight — their executables hold
        (and keep alive) the generation they were handed at launch, while
        this call and everything after it read the fork. Later writes in
        the same resolution donate again: they consume the fork, which no
        in-flight executable has seen. Disarm with ``clear_fork_mark`` if
        the anticipated write never materializes (e.g. every pack fell
        back to re-stacking)."""
        with self._lock:
            self._fork_pending = True

    def clear_fork_mark(self) -> None:
        with self._lock:
            self._fork_pending = False

    def is_live(self, user: Hashable, version: Hashable) -> bool:
        """True iff ``(user, version)`` already holds a slot, i.e. an
        ``ensure_rows`` call for it would be a pure hit — no row write, no
        LRU steal. The continuous dispatch loop uses this to decide whether
        a call needs the copy-on-write fork before launching over in-flight
        executables (hits read the current table generation freely; a miss
        means a row write, and a donated write would delete the generation
        an in-flight executable is reading)."""
        with self._lock:
            entry = self._map.get(user)
            return entry is not None and entry[0] == version

    @property
    def tables(self) -> dict[str, Any] | None:
        """The live per-boundary ``(capacity, ...)`` tables (None until the
        first write). Callers must treat the dict and its arrays as
        read-only and must not retain them across ``ensure_rows`` calls —
        the donated writer deletes superseded buffers."""
        return self._tables

    def __len__(self) -> int:
        with self._lock:
            return len(self._map)

    def stats(self) -> dict:
        with self._lock:
            boundary = ({k: int(t.nbytes) for k, t in self._tables.items()}
                        if self._tables is not None else {})
            return {
                "capacity": self.capacity,
                "resident": len(self._map),
                "free_slots": len(self._free),
                "writes": self.writes,
                "hits": self.hits,
                "recycles": self.recycles,
                "drops": self.drops,
                "overflows": self.overflows,
                "forks": self.forks,
                "quarantines": self.quarantines,
                "bytes": sum(boundary.values()),
                "boundary_bytes": boundary,
            }
