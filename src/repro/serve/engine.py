"""Per-request orchestration for the serving runtime (Fig. 2 made a system).

``ServingEngine`` compiles a (MaRI-rewritten) ranking graph into the
two-stage pipeline of ``repro.core.split`` and scores candidate pools
against cached user representations. This module is the *orchestration*
layer of the serve subsystem — queueing/coalescing lives in
``repro.serve.batcher`` and the bounded rep store in ``repro.serve.cache``.

Execution model — ONE row-wise stage-2 executable for everything:

  stage2(params, rep_table (U, ...), user_index (B,), candidate_feeds (B, ...))
      = residual_graph(params, {reps[user_index], candidates})

* a single request is the degenerate case U = 1 (``user_index`` all zero);
* a cross-user coalesced batch stacks the U users' cached stage-1 outputs
  into a rep table and lets each candidate row gather its own user's reps.

Both paths run the same row-parallel residual graph, so a row's score
does not depend on which users share its pack — but the executables
differ in shape (bucket, rep-table size, shard count), and XLA does not
promise bit-equal results across shapes: tiling and reduction order follow
the shape, and on a TPU float32 matmuls at default precision run as bf16
passes. Scores across shapes are therefore checked against the plain
float32 reference within a stated tolerance (``repro.serve.reference``);
bit-equality holds only where the same executable sees the same shapes,
as in lockstep vs continuous dispatch of one request stream.

Configuration is a ``repro.serve.plan.ServePlan`` — the frozen, validated,
JSON-serializable config spine shared by every entry point::

    engine = ServingEngine(graph, params, plan=ServePlan.preset("paper"))
    engine = ServingEngine(graph, params,
                           plan=ServePlan().evolve(graph__mode="uoi"))

``plan.graph`` picks the paradigm and MaRI-rewrite shape, ``plan.kernel``
the Pallas dispatch (fused ``mari_dense``, rep-table ``kernel_gather`` at
accumulator-init load, gather-at-load ``gather_attention`` boundaries),
``plan.batch`` the bucketing/coalescing envelope, ``plan.shard``
candidate-axis sharding on the ``repro.dist`` 'cand' mesh (single-process
``jax.sharding`` or SPMD across ``jax.distributed`` workers, optional int8
score gather), and ``plan.cache`` the bounded LRU user-rep store. Invalid
combinations are rejected or auto-resolved AT PLAN CONSTRUCTION (see the
resolution table in ``repro.serve.plan``) instead of failing late or
silently no-oping inside the engine.

Legacy keyword construction — ``ServingEngine(graph, params, mode=...,
use_pallas=..., ...)`` — still works as a thin shim that builds the
equivalent plan and emits a ``DeprecationWarning``; scores are identical
to the plan path by construction (proven by test).

One runtime-dependent adjustment stays here rather than in the plan: a
sharded engine rounds ``max_batch`` down to a shard-divisible power of
two.

Hot-path dispatch (``plan.cache.device_resident``) — the allocation-free
stage-2 pipeline:

* **device-resident rep tables** — cached stage-1 reps live in a
  slot-allocated ``DeviceRepStore``: ONE persistent ``(capacity, ...)``
  jax array per boundary, new users written as single donated
  ``.at[slot].set`` rows, evicted users merely recycling their slot
  integer. ``score_coalesced`` passes the persistent tables plus per-row
  *device slots* instead of re-concatenating a fresh ``(U, ...)`` table
  per bucket; the engine's ``mode="clip"`` gathers make dead or stale
  slots safe by construction.
* **donated bucket buffers** — candidate rows and the user index are
  filled into private per-pack host buffers (padding is one masked tail
  write), transferred, and donated to the stage-2 executable
  (``donate_argnums``), so steady-state serving performs zero fresh
  device allocations. Donated arguments are consumed: callers must never
  retain them.
* **async unpack** — launches are non-blocking and the call is a
  pipeline: after the table-write barrier, each pack is prepared and
  launched in turn, so the host packs bucket k+1 while the device
  computes bucket k; no result is blocked on until every pack is in
  flight, and scores materialize only when the reply is assembled.
* **stage profiler** — ``repro.serve.profile.StageProfiler`` splits every
  call into stage1 / pack / dispatch / device / unpack, surfaced via
  ``RankingService.stats()`` and the ``serve/<mode>/breakdown`` benchmark
  rows. Every phase is a ``repro.obs.trace.span``, so it is also a
  ``serve.<name>`` profiler annotation carrying ``group=<group id>``, and
  the executables are named ``serve_stage1`` / ``serve_stage2`` on the
  device's timeline.

Ordering contract: every device-table row write of a call completes
before any stage-2 launch of that call — so the donated table writer can
never delete a buffer an in-flight executable still reads. Concurrent
direct callers must serialize ``score``/``score_coalesced`` themselves
(the batcher's single worker thread already does).

Two-phase dispatch (the continuous batching loop's engine contract):
``begin_coalesced(reqs)`` runs stage 1 + packing + the table-write
barrier and launches every pack WITHOUT blocking, returning an opaque
in-flight handle; ``collect(handle)`` blocks, materializes, and slices
the per-request results. ``score_coalesced`` is exactly
``collect(begin_coalesced(reqs))``, so the lockstep and continuous paths
share one implementation and stay bit-identical by construction. The
engine tracks outstanding handles: a ``begin_coalesced`` call whose
users are all already resident overlaps freely (its packs read the
current table generation, which in-flight executables also hold). A call
that needs ANY device-table row write arms the store's copy-on-write
fork (``pipeline_forks`` counts these): the first write builds a NEW
table generation instead of donating the old one in place, so in-flight
executables keep reading the buffer they were handed while this call
reads the fork — overlap survives cold users at the cost of one table
copy. Either way the pipeline never drains mid-stream; results are
bit-identical because both generations carry identical rows for every
user a pack references.
"""
from __future__ import annotations

import dataclasses
import itertools
import time
import warnings
from typing import Hashable, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.common import next_pow2 as _next_pow2, prev_pow2
from repro.core.mari import mari_rewrite, convert_params
from repro.core.split import split_two_stage
from repro.ft.faults import CORRUPT, FaultInjector
from repro.ft.recovery import CircuitBreaker
from repro.graph.executor import Executor, USER_INDEX_FEED
from repro.graph.ir import Graph
from repro.mem import ColdRepStore, PromotionWorker, RepWarmer
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import DEFAULT_CAPACITY, Tracer, span
from repro.serve.cache import EVICT, DeviceRepStore, UserRepCache
from repro.serve.errors import FaultInjected
from repro.serve.plan import ServePlan
from repro.serve.profile import StageProfiler

# Free pack staging sets kept per bucket (``_take_pack_set``). A group
# stops taking requests once its rows reach ``max_batch``, so by rows it
# spans at most two packs; the continuous loop holds up to
# ``max_inflight`` (2 by default) groups in flight plus the one being
# packed: 2 x 3 = 6 sets a bucket cover the steady state. (Small pools
# whose packs the slot budget closes early can have more in flight; the
# sets past the cap are then dropped at collect and allocated afresh,
# which ``pack_buffers_allocated`` shows.) Worst-case idle host memory:
# 6 sets at each power-of-two bucket, under twice 6 sets at max_batch,
# i.e. 12 x max_batch x (candidate row bytes + 4); for paper-ranking at
# 4096 rows (two 500-wide float32 feeds and the int32 user index,
# 16.4 MB a set) that is 197 MB.
_PACK_SETS_PER_BUCKET = 6


@dataclasses.dataclass
class ServeRequest:
    user_id: int
    user_feeds: Mapping[str, jax.Array]      # leading dim 1
    candidate_feeds: Mapping[str, jax.Array]  # leading dim = n_candidates
    feature_version: int = 0                 # bump to invalidate cached reps


@dataclasses.dataclass
class ServeResult:
    scores: np.ndarray
    latency_ms: float            # wall time of the (possibly shared) batch
    n_batches: int               # stage-2 dispatches this request took part in
    user_cache_hit: bool
    stage1_ms: float = 0.0       # 0 when cached / single-stage
    coalesced: bool = False      # scored inside a cross-user batch
    degraded: bool = False       # candidate pool truncated under overload
    cold_hit: bool = False       # served from the host-RAM cold tier (no
    #                              stage-1 recompute, no hot/device slot)


def _precat_mari_weights(graph: Graph, params: dict) -> dict:
    """Pre-concatenate each ``mari_dense``'s batched-group weight blocks.

    The executor (and the Pallas kernel's ops layer) stream the batched
    side as ONE matmul ``concat(x_g) @ concat(W_g)``; without this, the
    weight concat is re-emitted inside every jitted call. Building the
    concatenated block once at engine-build time (stored as ``w_cat``
    beside the original blocks) removes it from the hot path. Scores are
    bit-identical either way — the streamed operand values are unchanged.
    """
    out = dict(params)
    for n in graph.nodes.values():
        if n.op != "mari_dense":
            continue
        p = params[n.name]
        if n.attrs.get("fragment"):
            if not n.attrs.get("precomputed_user"):
                continue          # batch-1-ness varies per segment: no fusion
            ws = [p[f"w_seg{i}"] for i in n.attrs["seg_param_idx"]]
        else:
            labels = [lab for lab, _ in n.attrs["groups"] if lab != "user"]
            ws = [p[f"w_{lab}"] for lab in labels]
        if len(ws) < 2:
            continue              # single block: nothing to concatenate
        out[n.name] = dict(p, w_cat=jnp.concatenate(ws, axis=0))
    return out


@dataclasses.dataclass
class _ReqInfo:                   # per-request working state inside a batch
    reps: Mapping[str, jax.Array]
    hit: bool
    stage1_ms: float
    chunks: list[tuple[dict, int]]
    slot_key: object
    cold_hit: bool = False        # reps came from the cold arena read


@dataclasses.dataclass(eq=False)
class _InFlight:
    """Opaque handle for a launched-but-uncollected ``begin_coalesced``
    call. ``eq=False``: identity semantics — the engine's outstanding list
    must distinguish two handles even for identical request batches."""
    reqs: Sequence[ServeRequest]
    infos: list
    packs: list
    launched: list                # per pack: its stage-2 outputs
    t0: float
    gid: int = 0                  # engine-wide group id (trace context)
    track: str | None = None      # synthetic trace track while outstanding
    slot: int = -1                # track slot, freed at collect
    slots_mask: list = dataclasses.field(default_factory=list)
    #                               per pack: True = device-slot fast path
    #                               (breaker outcome accounting at collect)
    bufsets: list = dataclasses.field(default_factory=list)
    #                               per pack: (uidx, cand) staging set,
    #                               back to the free list at collect


class ServingEngine:
    def __init__(self, graph: Graph, params: dict,
                 plan: ServePlan | str | None = None, *,
                 cache: UserRepCache | None = None,
                 cache_scope: Hashable | None = None,
                 **legacy_kwargs):
        """Compile ``graph`` for two-stage serving per ``plan``.

        ``plan`` is a ``ServePlan`` (or a preset name). ``cache`` /
        ``cache_scope`` let a host (``RankingService``) inject a SHARED
        ``UserRepCache``: cache keys are namespaced by ``cache_scope`` so
        several scenario engines can split one LRU budget without key
        collisions.

        Passing the old keyword knobs instead of ``plan`` still works: the
        legacy shim builds the equivalent plan (fail-fast validation
        included) and emits a ``DeprecationWarning``.
        """
        if plan is not None and legacy_kwargs:
            raise TypeError(
                f"pass plan= OR legacy keyword knobs, not both "
                f"(got plan and {sorted(legacy_kwargs)})")
        if isinstance(plan, str):
            plan = ServePlan.preset(plan)
        if plan is None:
            if legacy_kwargs:
                warnings.warn(
                    "ServingEngine keyword knobs are deprecated — pass "
                    "plan=ServePlan(...) (repro.serve.plan; "
                    "ServePlan.from_legacy_kwargs maps old names)",
                    DeprecationWarning, stacklevel=2)
            plan = ServePlan.from_legacy_kwargs(**legacy_kwargs)
        self.plan = plan
        mode = plan.graph.mode
        reparam_attention = plan.graph.reparam_attention
        fragment = plan.graph.fragment
        group_by_domain = plan.graph.group_by_domain
        two_stage = plan.graph.two_stage
        use_pallas = plan.kernel.use_pallas
        kernel_gather = plan.kernel.kernel_gather
        gather_attention = plan.kernel.gather_attention
        precat_weights = plan.kernel.precat_weights
        max_batch = plan.batch.max_batch
        shard_candidates = plan.shard.shard_candidates
        compress_scores = plan.shard.compress_scores

        self.mode = mode
        self.max_batch = max_batch
        self.min_bucket = plan.batch.min_bucket
        self.max_users_per_batch = plan.batch.max_users_per_batch
        if mode == "mari":
            conv = mari_rewrite(graph, reparam_attention=reparam_attention,
                                fragment=fragment,
                                group_by_domain=group_by_domain)
            self.graph = conv.graph
            self.params = convert_params(conv, params)
            self.conversion = conv
            exec_mode = "uoi"
        else:
            self.graph = graph
            self.params = params
            self.conversion = None
            exec_mode = mode
        # vani tiles user feeds into the batch — there is no user-only
        # subgraph to peel, so it stays single-stage.
        self.two_stage = (exec_mode == "uoi") if two_stage is None else two_stage
        self.outputs = list(self.graph.outputs)
        self._user_inputs = [n.name for n in self.graph.input_nodes()
                             if n.attrs.get("domain") == "user"]

        if self.two_stage:
            split = split_two_stage(self.graph)
            # The request contract partitions feeds by domain: user_feeds
            # carries exactly the domain=="user" inputs. A stage-1 input
            # outside that set (an uncolored, domain-less input pulled into
            # the user closure) could never be fed, so the split is not
            # servable for this graph.
            unservable = [n.name for n in split.stage1.input_nodes()
                          if n.attrs.get("domain") != "user"]
            if unservable and two_stage:
                raise ValueError(
                    f"two_stage=True but stage-1 needs non-user feeds "
                    f"{unservable}; give these inputs domain='user' or "
                    f"serve single-stage")
            if unservable:
                self.two_stage = False

        # -- candidate-axis sharding (stage 2): candidate rows + user index
        # split across shards, params and rep tables replicated. The mesh
        # and specs come from repro.dist; a single process over local
        # devices is the degenerate case of the multi-process topology. --
        self.shard_candidates = bool(shard_candidates)
        self._in_shardings = self._out_shardings = None
        self._n_shards = 1
        self._multiproc = False
        self.compress_scores = False
        # compress_scores without shard_candidates is rejected at plan
        # construction (PlanError) — no late engine check needed
        if shard_candidates:
            from repro.dist.sharding import candidate_pspecs
            from repro.dist.topology import candidate_mesh
            n_shards = (None if shard_candidates is True
                        else int(shard_candidates))
            # never shard wider than the caller's row budget allows: a
            # dispatch must give every shard >= 1 row within max_batch
            cap = prev_pow2(max_batch)
            self.mesh = candidate_mesh(cap if n_shards is None
                                       else min(n_shards, cap))
            self._n_shards = int(self.mesh.devices.size)
            self._multiproc = len({d.process_index
                                   for d in self.mesh.devices.flat}) > 1
            # buckets stay multiples of the shard count (pow2 / pow2):
            # no shard ever receives a ragged tail. The row cap itself must
            # divide evenly over the mesh, so a non-pow2 max_batch rounds
            # DOWN to the nearest power of two — never above the caller's
            # cap (the mesh was clamped to prev_pow2(max_batch) shards).
            if self._n_shards > 1:
                self.max_batch = prev_pow2(self.max_batch)
            self.min_bucket = min(max(self.min_bucket, self._n_shards),
                                  self.max_batch)
            self.compress_scores = compress_scores
            self._in_shardings, self._out_shardings = candidate_pspecs(
                self.mesh, replicate_out=(True if self._multiproc else None))
            if self.compress_scores:
                # the closing gather itself moves int8: stage 2 leaves its
                # scores device-sharded and the compressed all-gather (one
                # quantized collective) replicates them to every host
                from jax.sharding import NamedSharding, PartitionSpec as P
                self._out_shardings = NamedSharding(self.mesh, P("cand"))
        else:
            self.mesh = None

        if self.two_stage:
            self.split = split
            # rep-table contract: every user-side stage-2 input must be a
            # value stage 1 produces (boundary_specs names them) — a split
            # violating this could never be fed from the cache
            s2_user = {n.name for n in split.stage2.input_nodes()
                       if n.attrs.get("domain") == "user"}
            missing = s2_user - set(split.boundary_specs)
            if missing:
                raise ValueError(
                    f"stage-2 user inputs {sorted(missing)} are not in the "
                    f"split's boundary_specs — stage 1 cannot supply them")
            s1 = Executor(self.split.stage1, "uoi")

            def serve_stage1(params, feeds):
                return s1.run(params, feeds)

            self._stage1 = jax.jit(serve_stage1)
            self._stage1_inputs = {
                n.name for n in self.split.stage1.input_nodes()}
            batched_graph = self.split.stage2
            if self._in_shardings is not None:
                # the rep-table arg's shardings come from the split's own
                # boundary contract (per-entry rank-matched replication)
                # rather than a blanket spec — the table dict keys are
                # exactly the boundary names
                from repro.dist.sharding import named, rep_table_pspecs
                self._in_shardings = (
                    self._in_shardings[0],
                    named(self.mesh, rep_table_pspecs(split.boundary_specs)),
                    self._in_shardings[2], self._in_shardings[3])
        else:
            self.split = None
            self._stage1 = None
            self._stage1_inputs = None
            batched_graph = self.graph
        self.precat_weights = precat_weights
        if precat_weights:
            self.params = _precat_mari_weights(batched_graph, self.params)
        # kernel_gather without use_pallas was auto-resolved to False at
        # plan construction (with a PlanResolutionWarning), so no silent
        # `and use_pallas` masking is needed here anymore
        self.kernel_gather = kernel_gather
        # gather-aware attention works with or without Pallas: the executor
        # falls back to the jnp.take oracle off-TPU, so scores are identical
        # either way — only the memory profile needs the kernel
        self.gather_attention = gather_attention

        # -- rep cache + device tier (before _build_rowwise: stage-2 buffer
        # donation is only sound on the device-resident path) --
        # single-stage serving has no stage-1 outputs to reuse — the
        # "representation" is the raw feed dict, rebuilt per request — so
        # cache get/put there is pure bookkeeping overhead on the hot path
        # (BENCH_serve showed vani hit at 0.97x of cold); make it a no-op
        self.cache_user_reps = plan.cache.cache_user_reps and self.two_stage
        # an injected cache is SHARED (RankingService budget); cache_scope
        # namespaces this engine's keys inside it so same-valued user ids
        # from different scenarios cannot collide on wrong-shaped reps
        self.cache = cache if cache is not None else UserRepCache(
            max_users=plan.cache.max_cached_users)
        self._cache_scope = cache_scope
        # multi-process SPMD: every process would need the identical global
        # table state across asynchronous per-process writes — the device
        # tier stays off and packs re-stack replicated tables as before
        self.device_resident = (plan.cache.device_resident
                                and self.cache_user_reps
                                and not self._multiproc)
        self._device_store = None
        if self.device_resident:
            capacity = (plan.cache.device_slots
                        if plan.cache.device_slots is not None
                        else (plan.cache.max_cached_users or 64))
            table_shardings = (self._in_shardings[1]
                               if self._in_shardings is not None else None)
            self._device_store = DeviceRepStore(
                capacity, boundary_specs=self.split.boundary_specs,
                shardings=table_shardings)
            # recycle device slots in lockstep with the host tier: any
            # removal (LRU eviction, version supersede, invalidate, clear)
            # frees the user's slot for the next resident user
            self.cache.subscribe(self._device_store.drop)
        self._donate_stage2 = self.device_resident

        self._stage2 = self._build_rowwise(batched_graph, exec_mode,
                                           use_pallas)
        # multi-process: stage 2 consumes params as a globalized replica on
        # the cross-host mesh; stage 1 keeps the process-local copy
        self._params_s2 = self.params
        if self._multiproc:
            repl = self._in_shardings[0]
            self._params_s2 = jax.tree_util.tree_map(
                lambda v: self._globalize(v, repl), self.params)
        self._cgather = None
        if self.compress_scores:
            from jax.experimental.shard_map import shard_map
            from jax.sharding import PartitionSpec as P
            from repro.dist.compress import compressed_all_gather
            # check_rep off: the all-gathered result IS replicated, but the
            # checker can't prove it through the per-shard scale arithmetic
            self._cgather = jax.jit(shard_map(
                lambda x: compressed_all_gather(x, "cand"), mesh=self.mesh,
                in_specs=P("cand"), out_specs=P(), check_rep=False))

        self.stage1_calls = 0                 # trace counter for the split test
        self.stage2_calls = 0                 # total row-wise dispatches
        self.coalesced_calls = 0              # dispatches mixing >1 user slot
        self.pipeline_forks = 0               # copy-on-write table forks
        #                                       (begin_coalesced needed a row
        #                                       write while launches were in
        #                                       flight)
        self.h2d_bytes = 0                    # candidate + user-index buffer
        #                                       bytes handed to the device,
        #                                       padding rows included
        self.rep_stack_bytes = 0              # re-stacked user-rep table
        #                                       bytes, padding slots included
        self.pack_buffers_allocated = 0       # pack staging sets allocated
        self.pack_buffers_reused = 0          # packs filled into a free set
        # bucket -> free (uidx, cand) staging sets (_take_pack_set)
        self._pack_free: dict[int, list[tuple]] = {}
        self._inflight: list[_InFlight] = []  # launched, not yet collected
        # (U_dim, bucket) -> abstract (table, user_index, cand) arguments
        # of the first call at that signature (stage2_executables)
        self._batch_shapes: dict[tuple[int, int], tuple] = {}
        # first-seen candidate-feed signature {name: (dtype, row shape)} —
        # pack transfer buffers are shaped from it, so a later request
        # drifting from it must fail fast (see _chunk), not be silently
        # cast (or raise mid-call) by the buffer fill
        self._feed_sig: dict[str, tuple] | None = None
        self.profiler = StageProfiler()

        # -- observability (plan.obs): ring-buffer tracing + histogram
        # metrics (repro.obs). Tracing off keeps the hot path at a
        # `tracer is None` check; the cache tiers get the tracer for
        # eviction / slot-steal / fork instants. --
        self.tracer: Tracer | None = None
        if plan.obs.trace:
            self.tracer = Tracer(
                capacity=plan.obs.trace_capacity or DEFAULT_CAPACITY,
                sample_every=plan.obs.sample_every)
            self.cache.set_tracer(self.tracer)
            if self._device_store is not None:
                self._device_store.set_tracer(self.tracer)
        self.metrics: MetricsRegistry | None = None
        if plan.obs.metrics:
            self.metrics = MetricsRegistry()
            # the scattered counters, unified behind one snapshot():
            # gauges are sampled lazily, so registration costs nothing
            # on the hot path
            for name, fn in (
                    ("cache_hits", lambda: self.cache.hits),
                    ("cache_misses", lambda: self.cache.misses),
                    ("cache_evictions", lambda: self.cache.evictions),
                    ("stage1_calls", lambda: self.stage1_calls),
                    ("stage2_calls", lambda: self.stage2_calls),
                    ("rep_stack_bytes", lambda: self.rep_stack_bytes),
                    ("pack_buffers_allocated",
                     lambda: self.pack_buffers_allocated),
                    ("pack_buffers_reused",
                     lambda: self.pack_buffers_reused),
                    ("coalesced_calls", lambda: self.coalesced_calls),
                    ("pipeline_forks", lambda: self.pipeline_forks)):
                self.metrics.gauge(name, fn)
            self._group_wall_hist = self.metrics.histogram("group_wall_ms")
        else:
            self._group_wall_hist = None
        self._group_ids = itertools.count(1)  # group ids (new_group_id)
        self._group_slots: set[int] = set()  # outstanding trace tracks
        self._trace_req_seq = 0       # engine-side request sampling seq

        # -- fault tolerance (plan.ft): deterministic injection + the
        # stage-2 circuit breaker + device-tier quarantine. Off by default:
        # the hot path pays one `injector is None` check per site. --
        ftp = plan.ft
        self.fault_injector: FaultInjector | None = None
        if ftp.inject and ftp.sites:
            self.fault_injector = FaultInjector(
                ftp.sites, seed=ftp.seed, tracer=self.tracer)
            if self._device_store is not None:
                self._device_store.set_fault_injector(self.fault_injector)
        self.breaker: CircuitBreaker | None = None
        if ftp.breaker_failures > 0 and self._device_store is not None:
            self.breaker = CircuitBreaker(
                failures=ftp.breaker_failures,
                cooldown_ms=ftp.breaker_cooldown_ms,
                probes=ftp.breaker_probes,
                on_transition=self._on_breaker_transition)
        self.fallback_packs = 0       # packs the open breaker re-routed
        self.corruptions_detected = 0  # NaN-poisoned scores caught at collect
        if self.metrics is not None:
            for name, fn in (
                    ("faults_injected",
                     lambda: (self.fault_injector.total_fired
                              if self.fault_injector is not None else 0)),
                    ("breaker_opens",
                     lambda: (self.breaker.opens
                              if self.breaker is not None else 0)),
                    ("breaker_closes",
                     lambda: (self.breaker.closes
                              if self.breaker is not None else 0)),
                    ("breaker_fallback_packs", lambda: self.fallback_packs),
                    ("corruptions_detected",
                     lambda: self.corruptions_detected),
                    ("quarantines",
                     lambda: (self._device_store.quarantines
                              if self._device_store is not None else 0))):
                self.metrics.gauge(name, fn)

        # -- hierarchical memory tier (plan.mem, repro.mem): host-RAM cold
        # store + async promotion + bulk warming. Off by default. The cold
        # tier only makes sense under a live hot cache (plan resolution
        # already drops cold_tier without cache_user_reps; single-stage
        # engines force caching off above, which drops it here too). --
        self.cold_tier = plan.mem.cold_tier and self.cache_user_reps
        self._cold: ColdRepStore | None = None
        self._promoter: PromotionWorker | None = None
        self._warmer: RepWarmer | None = None
        self.cold_hits = 0            # requests served from the arena read
        self.cold_misses = 0          # full misses past an armed cold tier
        self.demotions = 0            # hot-LRU evictions caught by the arena
        if self.cold_tier:
            self._cold = ColdRepStore(plan.mem.cold_bytes)
            self._promoter = PromotionWorker(
                self._cold, self.cache,
                touches=plan.mem.promote_touches,
                window_s=plan.mem.promote_window_s, tracer=self.tracer)
            self._warmer = RepWarmer(self._warm_stage1, self._cold,
                                     batch=plan.mem.warm_batch,
                                     tracer=self.tracer)
            # hot-LRU evictions DEMOTE into the arena instead of being
            # discarded (fired outside the cache lock — see cache.py —
            # so the arena's leaf lock can never invert against it)
            self.cache.subscribe_removal(self._on_cache_removal)
            if self.metrics is not None:
                for name, fn in (
                        ("cold_hits", lambda: self.cold_hits),
                        ("cold_misses", lambda: self.cold_misses),
                        ("demotions", lambda: self.demotions),
                        ("promotions", lambda: self._promoter.promotions),
                        ("warmed_users", lambda: self._warmer.warmed),
                        ("cold_users", lambda: len(self._cold)),
                        ("cold_tier_bytes",
                         lambda: self._cold.stats()["bytes"])):
                    self.metrics.gauge(name, fn)

    # -- hierarchical memory tier hooks --------------------------------------
    def _warm_stage1(self, params, feeds):
        """The warmer dispatches the engine's OWN jitted stage-1 executable
        at the live path's (1, ...) feed shapes — warmed reps are
        bit-identical to what a request would have computed."""
        return self._stage1(params, {k: v for k, v in feeds.items()
                                     if k in self._stage1_inputs})

    def _on_cache_removal(self, user_id, version, reps, reason) -> None:
        """Hot-cache removal listener: evictions demote into the cold
        arena; supersede/invalidate/clear drop any cold copy too (a stale
        version must never be re-promoted). Runs outside the cache lock."""
        if self._cold is None:
            return
        if self._cache_scope is not None and not (
                isinstance(user_id, tuple) and len(user_id) == 2
                and user_id[0] == self._cache_scope):
            return                    # another scenario's keys in a shared
            #                           cache: not this arena's layout
        if reason == EVICT:
            self._cold.put((user_id, version), reps)
            self.demotions += 1
            if self.tracer is not None:
                self.tracer.instant("demote", user=user_id)
        else:
            self._cold.drop(user_id)

    def warm(self, items, feature_version: int = 0) -> int:
        """Bulk-precompute stage-1 reps straight into the cold tier.

        ``items`` is an iterable of ``(user_id, user_feeds)`` pairs (feeds
        at leading dim 1, same dict a ``ServeRequest`` would carry); a
        warmed user's first live request is a cold hit — one arena read,
        no stage-1 recompute. Returns the number of users warmed."""
        if not self.cold_tier:
            raise RuntimeError(
                "warm() requires plan.mem.cold_tier=True (and a two-stage "
                "engine with cache_user_reps)")
        triples = [(self._scoped_uid(uid), feature_version, feeds)
                   for uid, feeds in items]
        return self._warmer.warm(triples, self.params)

    def flush_promotions(self, timeout: float | None = 10.0) -> None:
        """Block until every cold-hit touch recorded so far has been
        processed by the promotion worker (deterministic tests/benches)."""
        if self._promoter is not None:
            self._promoter.flush(timeout)

    def mem_stats(self) -> dict:
        """One snapshot of the memory hierarchy (all tiers)."""
        if not self.cold_tier:
            return {"cold_tier": False}
        return {
            "cold_tier": True,
            "cold_hits": self.cold_hits,
            "cold_misses": self.cold_misses,
            "demotions": self.demotions,
            "cold": self._cold.stats(),
            "promote": self._promoter.stats(),
            "warm": {"warmed": self._warmer.warmed,
                     "stage1_launches": self._warmer.stage1_launches},
        }

    def _on_breaker_transition(self, old: str, new: str) -> None:
        trc = self.tracer
        if trc is not None:
            trc.instant({"open": "breaker_open",
                         "half_open": "breaker_half_open",
                         "closed": "breaker_close"}[new], previous=old)

    def _poke(self, site: str, **ctx):
        """Fault-injection hook: no-op unless the plan armed an injector."""
        inj = self.fault_injector
        if inj is None:
            return None
        return inj.poke(site, **ctx)

    def _quarantine_device_tier(self, reason: str) -> None:
        """A failed donated write/fork (or detected corruption) poisons
        the current table generation: invalidate it wholesale — the slot
        map clears, slots recycle, tables rebuild lazily from the host
        LRU on the next resolve — so a stale row is never served. Counts
        as one device-tier failure toward the breaker."""
        if self._device_store is not None:
            self._device_store.quarantine(reason=reason)
        if self.breaker is not None:
            self.breaker.record_failure()

    # -- build-time compilation helpers -------------------------------------
    @staticmethod
    def _globalize(x, sharding):
        """Lift a host value onto a (possibly cross-process) mesh: every
        process passes the identical global value and contributes its
        addressable shards."""
        x = np.asarray(x)
        return jax.make_array_from_callback(x.shape, sharding,
                                            lambda idx: x[idx])

    def _build_rowwise(self, graph: Graph, exec_mode: str, use_pallas: bool):
        """Jit the row-wise batched executable:
        (params, rep_table (U, ...), user_index (B,), cand (B, ...)) -> outs.

        ``rep_table`` holds stage-1 outputs (two-stage) or raw user feeds
        (single-stage fallback); every entry is gathered per candidate row,
        so row b computes against user ``user_index[b]``'s representations.
        With ``kernel_gather`` the entries feeding a Pallas ``mari_dense``
        accumulator init skip the explicit gather — the kernel indexes the
        stacked table by ``user_index`` at accumulator-init load time, so
        the gathered (B, units) block never materializes. With
        ``gather_attention`` the same applies to the decomposed-attention
        boundary tensors (keys / u_part / T): ``kernels.gather_einsum``
        indexes the stacked tables inside the contractions.
        """
        ex = Executor(graph, exec_mode, use_pallas=use_pallas,
                      kernel_gather=self.kernel_gather,
                      gather_attention=self.gather_attention)
        lazy = self.lazy_gather_inputs = ex.lazy_gather_inputs

        def serve_stage2(params, table, user_index, cand):
            # clip: padded rows carry a synthesized index (see _run_pack);
            # clamping guarantees even a garbage value reads a real slot
            # instead of wrapping (numpy) or NaN-filling (jax default)
            gathered = {k: (v if k in lazy
                            else jnp.take(v, user_index, axis=0,
                                          mode="clip"))
                        for k, v in table.items()}
            feeds = {**gathered, **cand}
            if lazy:
                feeds[USER_INDEX_FEED] = user_index
            return ex.run(params, feeds)

        kwargs = {}
        if self._in_shardings is not None:
            kwargs = dict(in_shardings=self._in_shardings,
                          out_shardings=self._out_shardings)
        if self._donate_stage2:
            # donated bucket buffers: user_index + candidate feeds are
            # single-use transfers under the device-resident path,
            # so XLA may alias their device buffers for outputs/temporaries
            # (zero fresh allocations in steady state). params and the
            # persistent rep tables are never donated — they outlive calls.
            kwargs["donate_argnums"] = (2, 3)
        return jax.jit(serve_stage2, **kwargs)

    # -- candidate mini-batching --------------------------------------------
    def _bucket(self, n: int) -> int:
        """Smallest power-of-two bucket >= n, clamped to
        [min_bucket, max_batch] and kept a multiple of the shard count —
        every pool size maps onto a small, fixed set of compiled shapes and
        no shard receives a ragged tail (repro.dist.topology)."""
        from repro.dist.topology import bucket_for
        return bucket_for(n, self._n_shards, min_bucket=self.min_bucket,
                          max_batch=self.max_batch)

    def _chunk(self, feeds: Mapping[str, jax.Array]) -> list[tuple[dict, int]]:
        """Split a candidate pool into raw (chunk, n_valid) pieces of at most
        ``max_batch`` rows. Chunks are host numpy views — packing copies
        them straight into each pack's transfer buffers, so no per-chunk
        device arrays are ever created. Padding happens per *pack*
        (possibly shared with other users' chunks), not per chunk.

        The candidate-feed signature (names, row shapes, dtypes) is
        pinned by the first request the engine sees: the per-pack
        transfer buffers are shaped from it, and a numpy slice
        assignment would silently cast a drifting dtype (or raise on a
        trailing-shape mismatch only after earlier packs launched) — so
        drift is rejected here, before any pack of the call launches."""
        arrs = {k: np.asarray(v) for k, v in feeds.items()}
        sig = {k: (v.dtype, tuple(v.shape[1:])) for k, v in arrs.items()}
        if self._feed_sig is None:
            self._feed_sig = sig
        elif sig != self._feed_sig:
            drift = sorted(k for k in sig.keys() | self._feed_sig.keys()
                           if sig.get(k) != self._feed_sig.get(k))
            raise ValueError(
                f"candidate feed signature drifted from the engine's "
                f"first request on {drift}: expected "
                f"{ {k: self._feed_sig.get(k) for k in drift} }, got "
                f"{ {k: sig.get(k) for k in drift} } — per-engine "
                f"candidate feeds must keep stable names, row shapes "
                f"and dtypes (transfer buffers are shaped from the "
                f"first request's signature)")
        n = next(iter(arrs.values())).shape[0]
        out = []
        for lo in range(0, n, self.max_batch):
            hi = min(lo + self.max_batch, n)
            out.append(({k: v[lo:hi] for k, v in arrs.items()}, hi - lo))
        return out

    @property
    def stage2_compilations(self) -> int:
        """Number of compiled batched-stage executables (distinct
        (rep-table, bucket) shape pairs)."""
        return self._stage2._cache_size()

    def stage2_executables(self) -> dict[tuple[int, int], jax.stages.Compiled]:
        """The compiled stage-2 executable of every (rep-table rows,
        bucket) signature served so far — to inspect what the device runs
        (``as_text()``: are the Pallas kernels in it, as
        ``tpu_custom_call``?; ``output_shardings``). Each is lowered again
        from its first call's shapes; the persistent compilation cache,
        where on, turns the compile into a read."""
        params = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), self._params_s2)
        return {key: self._stage2.lower(params, *args).compile()
                for key, args in self._batch_shapes.items()}

    @property
    def cache_evictions(self) -> int:
        """User-rep entries dropped by the LRU bound (capacity signal)."""
        return self.cache.evictions

    @property
    def device_store(self) -> DeviceRepStore | None:
        """The device rep tier (None unless ``device_resident`` is live)."""
        return self._device_store

    # -- stage 1: user-side partial evaluation ------------------------------
    def _scoped_uid(self, user_id: Hashable) -> Hashable:
        """Namespace a user id for the (possibly shared) rep cache."""
        return (user_id if self._cache_scope is None
                else (self._cache_scope, user_id))

    def _user_reps(self, req: ServeRequest, gid: int
                   ) -> tuple[Mapping[str, jax.Array], bool, float, bool]:
        key = (self._scoped_uid(req.user_id), req.feature_version)
        if self.cache_user_reps:
            reps = self.cache.get(key)
            if reps is not None:
                return reps, True, 0.0, False
            if self._cold is not None:
                creps = self._cold.get(key)
                if creps is not None:
                    # cold hit: serve straight from the arena read — no
                    # stage-1 recompute, no hot put (the async promotion
                    # worker decides residency OFF the request path, so a
                    # one-shot tail user never evicts a hot user), no
                    # device slot (cold-served packs take the re-stacking
                    # route — see _resolve_device_slots)
                    self.cold_hits += 1
                    self._promoter.touch(key)
                    return creps, False, 0.0, True
                self.cold_misses += 1
        if self.two_stage:
            self._poke("stage1", user=req.user_id)
            with span("stage1", phase="stage1", tracer=self.tracer,
                      profiler=self.profiler, group=gid,
                      user=req.user_id) as sp:
                feeds = {k: v for k, v in req.user_feeds.items()
                         if k in self._stage1_inputs}
                reps = self._stage1(self.params, feeds)
                with span("stage1_wait", tracer=self.tracer, group=gid):
                    jax.block_until_ready(reps)
                self.stage1_calls += 1
            ms = sp.seconds * 1e3
        else:
            # single-stage: the "representation" is the raw user feed dict
            # (never cached — cache_user_reps is forced off above: there is
            # nothing to reuse, so cache bookkeeping was pure overhead)
            reps, ms = dict(req.user_feeds), 0.0
        if self.cache_user_reps:
            self.cache.put(key, reps)
        return reps, False, ms, False

    # -- scoring ------------------------------------------------------------
    def score(self, req: ServeRequest) -> ServeResult:
        """Score one request — the U=1 degenerate case of the coalesced path
        (same executable family, different shape)."""
        return self.score_coalesced([req])[0]

    def score_coalesced(self, reqs: Sequence[ServeRequest], *,
                        gid: int | None = None) -> list[ServeResult]:
        """Score several users' requests, coalescing candidate chunks that
        share a power-of-two bucket into single cross-user stage-2 calls.

        The call runs as a write barrier followed by a pipeline: ALL
        device-table row writes happen first (so donated table
        generations are never deleted under an in-flight executable),
        then packs are prepared-and-launched one by one — launches are
        non-blocking, so the host packs bucket k+1 while the device
        computes bucket k — and a final collect sweep blocks,
        materializes, and slices per-request views (async unpack).

        This is exactly ``collect(begin_coalesced(reqs))`` — the lockstep
        degenerate case of the two-phase API, so lockstep and continuous
        dispatch share one implementation and stay bit-identical."""
        return self.collect(self.begin_coalesced(reqs, gid=gid))

    def new_group_id(self) -> int:
        """A fresh group id (the ``group`` arg of every span and trace
        event of one ``begin_coalesced`` call). A caller that records
        spans before the group exists (the batcher's wait and linger)
        takes one here and hands it to ``begin_coalesced``."""
        return next(self._group_ids)

    def begin_coalesced(self, reqs: Sequence[ServeRequest], *,
                        gid: int | None = None) -> _InFlight:
        """Phase 1 of the two-phase dispatch: stage 1 + packing + the
        table-write barrier, then launch every pack WITHOUT blocking.

        Returns an in-flight handle for ``collect``. While a handle is
        outstanding, further ``begin_coalesced`` calls overlap with it
        freely: all-resident calls (the Zipf-hot steady state) read the
        same table generation the in-flight executables hold; a call that
        needs a device-table row write arms the store's copy-on-write
        fork (``pipeline_forks``) — the write builds a NEW generation
        instead of donating the old buffer, which in-flight executables
        are still reading, so cold users cost one table copy instead of
        a pipeline drain. ``gid``: an id from ``new_group_id``, or None
        for a fresh one."""
        t0 = time.perf_counter()
        trc = self.tracer
        if gid is None:
            gid = self.new_group_id()
        g_slot, g_track = -1, None
        if trc is not None:
            # one synthetic trace track per OUTSTANDING group: the lowest
            # free slot, released at collect — two overlapped groups land
            # on two tracks, so their concurrency is visible in Perfetto
            # (begin/collect are serialized by the engine contract, so the
            # slot set needs no lock)
            g_slot = 0
            while g_slot in self._group_slots:
                g_slot += 1
            self._group_slots.add(g_slot)
            g_track = f"group:{g_slot}"
            trc.begin("group", track=g_track, group=gid, reqs=len(reqs))
        try:
            with span("begin_coalesced", tracer=trc, group=gid,
                      reqs=len(reqs)) as sp:
                handle = self._begin_coalesced_body(reqs, t0, gid, g_track,
                                                    g_slot)
                sp.set(packs=len(handle.packs))
            return handle
        except BaseException:
            # close the group span on ANY failure after it opened — stage 1,
            # packing, or launch — so traces stay B/E-balanced and the
            # synthetic track slot is released for the next group
            if trc is not None:
                trc.end("group", track=g_track, group=gid, error=True)
                self._group_slots.discard(g_slot)
            raise

    def _begin_coalesced_body(self, reqs: Sequence[ServeRequest], t0: float,
                              gid: int, g_track: str | None, g_slot: int
                              ) -> _InFlight:
        prof = self.profiler
        trc = self.tracer
        infos: list[_ReqInfo] = []
        for ri, req in enumerate(reqs):
            reps, hit, s1ms, chit = self._user_reps(req, gid)
            if trc is not None:
                self._trace_req_seq += 1
                if trc.sampled(self._trace_req_seq):
                    trc.instant("cache_hit" if hit
                                else "cold_hit" if chit else "cache_miss",
                                group=gid, user=req.user_id)
                    if not hit and not chit and self._cold is not None:
                        trc.instant("cold_miss", group=gid,
                                    user=req.user_id)
            infos.append(_ReqInfo(
                reps=reps, hit=hit, stage1_ms=s1ms, cold_hit=chit,
                chunks=self._chunk(req.candidate_feeds),
                # slot dedup follows the cache: with it on, every request
                # with one (user, version) key resolves to the SAME cached
                # reps, so they can share a rep-table slot. Without a cache
                # (incl. single-stage engines) reps are per-request values
                # with no canonical copy per key — per-request slots keep
                # each row on its own request's reps unconditionally, at
                # the cost of repeat users occupying one slot per request.
                slot_key=((req.user_id, req.feature_version)
                          if self.cache_user_reps else ri)))

        # greedy packing in arrival order: a pack holds chunks from as many
        # requests as fit the row budget and the slot budget
        items = [(ri, chunk, n) for ri, info in enumerate(infos)
                 for chunk, n in info.chunks]
        # (items w/ slot idx, slot reps, slot cache keys)
        packs: list[tuple[list, list, list]] = []
        cur: list = []
        cur_rows = 0
        cur_slots: dict = {}                   # slot_key -> slot index
        cur_reps: list = []                    # slot index -> reps
        cur_keys: list = []                    # slot index -> slot_key
        for ri, chunk, n in items:
            key = infos[ri].slot_key
            full = cur and (
                cur_rows + n > self.max_batch
                or (key not in cur_slots
                    and len(cur_slots) >= self.max_users_per_batch))
            if full:
                packs.append((cur, cur_reps, cur_keys))
                cur, cur_rows, cur_slots = [], 0, {}
                cur_reps, cur_keys = [], []
            if key not in cur_slots:
                cur_slots[key] = len(cur_reps)
                cur_reps.append(infos[ri].reps)
                cur_keys.append(key)
            cur.append((ri, cur_slots[key], chunk, n))
            cur_rows += n
        if cur:
            packs.append((cur, cur_reps, cur_keys))

        # continuous-loop write-under-flight guard: if ANY slot key of this
        # call is not already resident, the write barrier below will issue
        # a table-row write — and a DONATED write would delete the
        # generation every outstanding executable is still reading. Arm the
        # store's copy-on-write fork instead: the first write builds a new
        # generation (old buffer stays alive for the in-flight launches),
        # later writes of this call donate the unpublished fork in place.
        # All-resident calls (the Zipf-hot steady state) skip even the copy.
        cold_keys = {info.slot_key for info in infos if info.cold_hit}
        forked = False
        if self._device_store is not None and self._inflight:
            # cold-served keys never get a table-row write (their packs
            # re-stack), so they cannot trigger the fork
            keys = {info.slot_key for info in infos} - cold_keys
            if any(not self._device_store.is_live(self._scoped_uid(u), v)
                   for u, v in keys):
                self.pipeline_forks += 1
                self._device_store.fork_next_write()
                forked = True
                if trc is not None:
                    trc.instant("fork_armed", group=gid,
                                inflight=len(self._inflight))

        # write barrier: EVERY table-row write of the call happens here,
        # before any launch — in-place donated writes must never run under
        # an in-flight executable (the fork above covers the case where
        # launches ARE outstanding)
        with span("slots", phase="pack", tracer=trc, profiler=prof,
                  group=gid):
            dslots = self._resolve_device_slots(packs, cold_keys)
        if forked:
            # the anticipated write may never have happened (e.g. every
            # pack fell back to re-stacking): a stale mark must not fork
            # some later, unrelated write
            self._device_store.clear_fork_mark()

        # pipelined prepare+launch: launches are non-blocking, so the
        # buffer fill + transfer of pack k+1 overlaps the device compute
        # of pack k. Each pack holds its staging set until collect
        # (_prepare_pack) — pack k's host->device copy may still be
        # pending on the device stream here.
        launched = []
        bufsets = []
        try:
            for (pack_items, slot_reps, _), ds in zip(packs, dslots):
                total = sum(n for _, _, _, n in pack_items)
                bucket = self._bucket(total)
                with span("pack", phase="pack", tracer=trc, profiler=prof,
                          group=gid, bucket=bucket, rows=total,
                          pad=bucket - total, users=len(slot_reps),
                          path="slots" if ds is not None else "restack"):
                    prep = self._prepare_pack(pack_items, slot_reps, ds,
                                              bucket, gid, bufsets)
                launched.append(self._launch_pack(
                    prep, on_slots=ds is not None, gid=gid))
        except BaseException:
            # never leave untracked launches behind: a later call's table
            # write could otherwise run under them. The call's staging
            # sets are dropped, not recycled.
            for out in launched:
                jax.block_until_ready(out)
            raise

        handle = _InFlight(reqs=reqs, infos=infos, packs=packs,
                           launched=launched, t0=t0, gid=gid,
                           track=g_track, slot=g_slot,
                           slots_mask=[ds is not None for ds in dslots],
                           bufsets=bufsets)
        self._inflight.append(handle)
        return handle

    def _drain_inflight(self) -> None:
        """Block until every outstanding launch has finished executing.
        Handles stay collectible — their results are simply already
        materialized when ``collect`` runs."""
        for h in self._inflight:
            for out in h.launched:
                jax.block_until_ready(out)

    def poll(self, handle: _InFlight) -> bool:
        """Non-blocking readiness probe: True when ``collect(handle)``
        would not wait on the device (every launch's outputs are ready).
        Conservatively False on backends whose arrays expose no
        readiness — callers fall back to collecting at the blocking
        points. This is what lets the continuous loop harvest a finished
        group the moment it completes instead of holding its results
        through the next group's linger window."""
        for out in handle.launched:
            for leaf in jax.tree_util.tree_leaves(out):
                ready = getattr(leaf, "is_ready", None)
                if ready is None or not ready():
                    return False
        return True

    def collect(self, handle: _InFlight) -> list[ServeResult]:
        """Phase 2 of the two-phase dispatch: block on the handle's
        launches, materialize scores to host, and slice per-request
        results. Handles may be collected in any order; each exactly
        once."""
        trc = self.tracer
        try:
            self._inflight.remove(handle)
        except ValueError:
            raise RuntimeError(
                "collect() on a handle that is not in flight (already "
                "collected, or from another engine)") from None
        try:
            with span("collect", tracer=trc, group=handle.gid,
                      packs=len(handle.packs)):
                return self._collect_body(handle)
        except BaseException:
            # a mid-sweep failure (injected fault, detected corruption)
            # must not leave untracked launches behind, and the group
            # trace span must close so traces stay B/E-balanced. The
            # group's staging sets are dropped, not recycled.
            for out in handle.launched:
                jax.block_until_ready(out)
            if trc is not None and handle.track is not None:
                trc.end("group", track=handle.track, group=handle.gid,
                        error=True)
                self._group_slots.discard(handle.slot)
            raise

    def _collect_body(self, handle: _InFlight) -> list[ServeResult]:
        prof = self.profiler
        trc = self.tracer
        reqs, infos, packs, launched = (handle.reqs, handle.infos,
                                        handle.packs, handle.launched)
        slots_mask = handle.slots_mask or [False] * len(packs)
        detect = self.fault_injector is not None

        # collect sweep: block on device, materialize, slice per request
        per_req_scores: list[list[np.ndarray]] = [[] for _ in reqs]
        per_req_packs = [0] * len(reqs)
        for (pack_items, _, _), out, on_slots in zip(
                packs, launched, slots_mask):
            total = sum(n for _, _, _, n in pack_items)
            with span("device", phase="device", tracer=trc,
                      profiler=prof, group=handle.gid):
                jax.block_until_ready(out)
            act = self._poke("collect", group=handle.gid)
            with span("unpack", phase="unpack", tracer=trc, profiler=prof,
                      group=handle.gid):
                scores = np.concatenate(
                    [np.asarray(out[o]) for o in self.outputs],
                    axis=-1)[:total]
            if act is CORRUPT:
                scores = np.full_like(scores, np.nan)
            if detect and not np.isfinite(scores).all():
                # corruption detection: NaN-poisoned payloads (injected
                # at transfer_copy / slot_write / collect) surface here —
                # the corrupted response is failed typed, never served
                self.corruptions_detected += 1
                if trc is not None:
                    trc.instant("corruption_detected", group=handle.gid,
                                path="slots" if on_slots else "restack")
                if on_slots:
                    # the device tier may hold the poisoned row: wipe the
                    # generation so a retry rebuilds from the host LRU
                    self._quarantine_device_tier(
                        "corrupted scores detected at collect")
                raise FaultInjected(
                    "corrupted stage-2 scores detected at collect",
                    site="collect")
            if on_slots and self.breaker is not None:
                self.breaker.record_success()
            touched = set()
            offset = 0
            for ri, _, _, n in pack_items:
                per_req_scores[ri].append(scores[offset:offset + n])
                offset += n
                touched.add(ri)
            for ri in touched:
                per_req_packs[ri] += 1

        # every pack's outputs are ready, so its host->device copies have
        # run: the staging sets may be refilled by later packs
        for uidx_buf, cand_bufs in handle.bufsets:
            free = self._pack_free.setdefault(len(uidx_buf), [])  # bucket
            if len(free) < _PACK_SETS_PER_BUCKET:
                free.append((uidx_buf, cand_bufs))

        wall_ms = (time.perf_counter() - handle.t0) * 1e3
        if self._group_wall_hist is not None:
            self._group_wall_hist.record(wall_ms)
        if trc is not None and handle.track is not None:
            trc.end("group", track=handle.track, group=handle.gid)
            self._group_slots.discard(handle.slot)
        return [ServeResult(
            scores=np.concatenate(per_req_scores[ri], axis=0),
            latency_ms=wall_ms, n_batches=per_req_packs[ri],
            user_cache_hit=infos[ri].hit,
            stage1_ms=infos[ri].stage1_ms, coalesced=len(reqs) > 1,
            cold_hit=infos[ri].cold_hit)
            for ri in range(len(reqs))]

    # -- pack preparation ----------------------------------------------------
    def _resolve_device_slots(self, packs: list,
                              cold_keys: set = frozenset()
                              ) -> list[list[int] | None]:
        """Map every pack's slot keys to device-table slots (one donated
        row write per user not already resident). ``None`` per pack when
        the device tier is off or that pack overflowed capacity — the pack
        then falls back to the re-stacking path (same rows; scores within
        the stated tolerance, since the table shapes differ).

        A user appearing under TWO feature versions in one call also
        forces every pack carrying that user onto the fallback: the
        device store keeps one slot per user, so resolving the second
        version would rewrite the slot the first version's rows read —
        within a pack (both keys collapsing to one slot) and across packs
        (a later barrier write clobbering a row an earlier pack
        references). Re-stacking keeps per-version tables, so each
        version's rows read that version's reps.

        Every device-resolved user of the CALL is protected while
        resolving: a later pack's write may never steal a slot an
        earlier (already prepared) pack still references.

        ``cold_keys`` are slot keys served from the cold tier this call:
        their packs also fall back — a cold-served (by policy, tail) user
        must not cost a device-table row write or steal a hot user's
        slot, and with no hot-cache entry there is no eviction listener
        to ever free the slot in lockstep."""
        if self._device_store is None:
            return [None] * len(packs)
        if self.breaker is not None and not self.breaker.allow():
            # breaker open: route every pack through the re-stacking
            # fallback (same rows, same scores within tolerance) instead of touching the device tier;
            # after the cooldown, allow() itself flips to half-open and
            # lets probe traffic back onto the fast path
            self.fallback_packs += len(packs)
            if self.tracer is not None:
                self.tracer.instant("breaker_fallback", packs=len(packs))
            return [None] * len(packs)
        ver_of: dict = {}
        conflicted = set()
        for _, _, slot_keys in packs:
            # with the device tier live, cache_user_reps is on, so every
            # slot key is a (user_id, feature_version) cache key
            for uid, ver in slot_keys:
                if ver_of.setdefault(uid, ver) != ver:
                    conflicted.add(uid)
        per_pack = []
        protect: list = []
        for _, slot_reps, slot_keys in packs:
            if (any(uid in conflicted for uid, _ in slot_keys)
                    or (cold_keys
                        and any(k in cold_keys for k in slot_keys))):
                per_pack.append(None)
                continue
            triples = [(self._scoped_uid(uid), ver, reps)
                       for (uid, ver), reps in zip(slot_keys, slot_reps)]
            per_pack.append(triples)
            protect.extend(u for u, _, _ in triples)
        out = []
        poisoned = False
        for triples in per_pack:
            if triples is None or poisoned:
                out.append(None)
                continue
            try:
                slots = self._device_store.ensure_rows(triples,
                                                       protect=protect)
            except Exception as e:
                # a failed donated write/fork may have left the current
                # table generation inconsistent: quarantine it (slots
                # recycle, tables rebuild lazily from the host LRU) and
                # route this call's remaining packs through the
                # re-stacking fallback — the request still succeeds, with
                # the same scores within tolerance, while the breaker accumulates the
                # failure
                self._quarantine_device_tier(
                    f"ensure_rows failed: {type(e).__name__}: {e}")
                poisoned = True
                out.append(None)
                continue
            out.append(slots if all(s is not None for s in slots) else None)
        return out

    def _prepare_pack(self, pack_items: list, slot_reps: list,
                      dslots: list[int] | None, bucket: int, gid: int,
                      bufsets: list):
        """Assemble one stage-2 call's arguments at ``bucket`` rows (spans
        ``stack``, the re-stack of several users' reps into one table,
        ``fill``, the buffer fill, and ``h2d``, the host->device copies).

        ``pack_items`` is a list of (req idx, slot idx, cand chunk,
        n_valid); ``slot_reps`` maps slot idx -> that user's rep dict;
        ``dslots`` maps slot idx -> persistent device-table slot (or None
        for the re-stacking path). Candidate rows and the user index are
        filled into a host staging set private to this pack until it is
        collected (``_take_pack_set``) — padding is one masked tail write
        — then transferred. The set is appended to ``bufsets``, the call's
        list that rides on its ``_InFlight`` handle."""
        self._poke("pack")
        n_slots = len(slot_reps)

        if dslots is not None:
            # device-resident: pass the persistent (capacity, ...) tables;
            # rows address their user's live device slot directly
            table = self._device_store.tables
            u_dim = self._device_store.capacity
            slot_ids = dslots
        else:
            # re-stack a fresh table: one row-block per slot, padded to a
            # pow2 slot count so the executable family stays small
            u_dim = _next_pow2(n_slots)
            if n_slots == 1 and u_dim == 1:
                table = dict(slot_reps[0])
            else:
                padded = slot_reps + [slot_reps[0]] * (u_dim - n_slots)
                nbytes = u_dim * sum(v.nbytes for v in slot_reps[0].values())
                with span("stack", tracer=self.tracer, group=gid,
                          users=n_slots, bytes=nbytes):
                    table = {k: jnp.concatenate([r[k] for r in padded],
                                                axis=0)
                             for k in slot_reps[0]}
                self.rep_stack_bytes += nbytes
            slot_ids = list(range(n_slots))

        with span("fill", tracer=self.tracer, group=gid) as sp:
            uidx_buf, cand_bufs, reused = self._take_pack_set(bucket)
            bufsets.append((uidx_buf, cand_bufs))
            sp.set(reused=reused)
            offset = 0
            for _, slot, chunk, n in pack_items:
                uidx_buf[offset:offset + n] = slot_ids[slot]
                for k, buf in cand_bufs.items():
                    buf[offset:offset + n] = chunk[k]
                offset += n
            if offset < bucket:
                # padding rows duplicate the LAST real row exactly — user
                # slot and candidate row — in one masked tail write per
                # buffer, so pad scores are copies of a real score (a
                # cross-user slot-0 / tail-candidate combination could
                # exceed max|real score| and inflate the compress_scores
                # int8 quantization scale past the verified error bound)
                uidx_buf[offset:] = uidx_buf[offset - 1]
                for buf in cand_bufs.values():
                    buf[offset:] = buf[offset - 1]

        # the buffers above are PRIVATE to this pack until it is
        # collected — nothing may mutate them before. The host->device
        # copy is enqueued on the device stream and executes
        # asynchronously, behind every in-flight executable; the runtime
        # keeps the source buffer alive until then, but it cannot protect
        # it from being overwritten. Refilling a set before its pack was
        # collected let a later same-bucket pack win that race under the
        # continuous loop, silently swapping candidate rows between
        # overlapped groups (caught by the bit-identity suite). Once the
        # pack's outputs are ready its copies have run, so collect hands
        # the set back to the free list for a later pack to refill;
        # error paths drop it instead.
        if self._poke("transfer_copy") is CORRUPT:
            # detectable-corruption sentinel: NaN-poison the float
            # candidate buffers — NaN propagates through the stage-2
            # matmuls into the scores and is caught at collect, so a
            # corrupted transfer is never silently served (the set may
            # be refilled later all the same: a fill rewrites every row)
            for buf in cand_bufs.values():
                if np.issubdtype(buf.dtype, np.floating):
                    buf.fill(np.nan)
        self.h2d_bytes += uidx_buf.nbytes + sum(
            b.nbytes for b in cand_bufs.values())
        with span("h2d", tracer=self.tracer, group=gid):
            if self._multiproc:
                # SPMD: every process holds the identical host values;
                # lift them onto the cross-process mesh (replicated
                # tables, sharded candidate rows + index)
                repl, _, shard, _ = self._in_shardings
                table = {k: self._globalize(v, repl)
                         for k, v in table.items()}
                cand = {k: self._globalize(v, shard)
                        for k, v in cand_bufs.items()}
                uidx_arr = self._globalize(uidx_buf, shard)
            else:
                cand = {k: jnp.array(v) for k, v in cand_bufs.items()}
                uidx_arr = jnp.array(uidx_buf)

        # first call at a new (rep-table, bucket) signature compiles: its
        # abstract arguments feed stage2_executables, and the dispatch
        # span marks the call
        first_shape = (u_dim, bucket) not in self._batch_shapes
        if first_shape:
            self._batch_shapes[(u_dim, bucket)] = jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (table, uidx_arr, cand))
        return table, uidx_arr, cand, n_slots, first_shape

    def _take_pack_set(self, bucket: int) -> tuple[np.ndarray, dict, bool]:
        """A staging set for one pack at ``bucket`` rows: the int32 user
        index and one buffer per candidate feed, shaped from the pinned
        ``_feed_sig``. A free set collected from an earlier pack when
        there is one (``reused`` True), else fresh memory, which the
        fill then page-faults in row by row."""
        free = self._pack_free.get(bucket)
        if free:
            self.pack_buffers_reused += 1
            return (*free.pop(), True)
        self.pack_buffers_allocated += 1
        return (np.empty((bucket,), np.int32),
                {k: np.empty((bucket,) + shape, dtype)
                 for k, (dtype, shape) in self._feed_sig.items()},
                False)

    # -- dispatch ------------------------------------------------------------
    def _launch_pack(self, prep, *, on_slots: bool, gid: int) -> dict:
        """Launch one prepared pack and return its outputs. The launch
        only enqueues stage 2 (and the optional compressed gather):
        results stay on the device until ``collect`` waits for them and
        materializes them. ``on_slots``
        marks the device-resident fast path: a failed launch there counts
        toward the circuit breaker."""
        table, uidx_arr, cand, n_slots, first_shape = prep
        self.stage2_calls += 1
        if n_slots > 1:
            self.coalesced_calls += 1
        try:
            self._poke("stage2_dispatch")
            with span("dispatch", phase="dispatch", tracer=self.tracer,
                      profiler=self.profiler, group=gid,
                      bucket=int(uidx_arr.shape[0]),
                      first_shape=first_shape):
                out = self._stage2(self._params_s2, table, uidx_arr, cand)
                if self._cgather is not None:
                    # opt-in int8 result collection: the only cross-shard
                    # movement of the step runs quantized
                    # (repro.dist.compress)
                    out = {k: self._cgather(v) for k, v in out.items()}
                return out
        except Exception:
            if on_slots and self.breaker is not None:
                self.breaker.record_failure()
            raise

    def invalidate_user(self, user_id: int) -> None:
        self.cache.invalidate_user(self._scoped_uid(user_id))
        if self._cold is not None:
            # a warmed-but-never-promoted user lives ONLY in the cold
            # arena — the hot cache fires no removal listener for it
            self._cold.drop(self._scoped_uid(user_id))

    def close(self) -> None:
        # uncollected begin_coalesced launches must not outlive the engine
        self._drain_inflight()
        self._inflight.clear()
        if self._promoter is not None:
            self._promoter.stop()
