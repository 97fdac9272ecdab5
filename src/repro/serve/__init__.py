"""Serving runtime for ranking graphs — the inference workflow of Fig. 2
grown into an async, multi-user subsystem:

* ``engine``  — ``ServingEngine``: per-request orchestration. Stage 1 (the
  user-only precompute subgraph of ``repro.core.split``) runs once per
  (user, feature_version) and its outputs are cached; stage 2 (the batched
  residual) is ONE row-wise executable family — each candidate row gathers
  its own user's cached reps via a per-row user index — so a single request
  (U=1) and a cross-user coalesced batch run the same code. Options: fused Pallas ``mari_dense`` dispatch
  (optionally with the kernel-side user-rep gather), build-time
  grouped-weight pre-concatenation, and candidate-axis sharding on the
  ``repro.dist`` 'cand' mesh — single-process ``jax.sharding`` or SPMD
  across ``jax.distributed`` worker processes (rep tables replicated,
  shard-aligned buckets, optional int8-compressed score gather).
* ``batcher`` — ``CoalescingBatcher``: async request queue that packs
  candidate chunks from different users into shared power-of-two stage-2
  buckets (cross-user batching), with SLO classes — deadline-tagged
  requests jump the FIFO and shrink the linger window. Dispatch is a
  continuous loop: group k+1 is formed and launched (two-phase engine
  API) while group k executes on device, and SLO-tiered admission
  control sheds (typed ``AdmissionError``) or degrades best_effort work
  before deadline work under overload.
* ``cache``   — ``UserRepCache``: bounded LRU user-representation store
  with eviction accounting, removal listeners, byte accounting and
  per-user invalidation; ``DeviceRepStore``: the slot-allocated
  device-resident tier over it — one live (capacity, ...) device table
  per stage-2 boundary, donated single-row writes, slot recycling — so
  the coalesced hot path feeds persistent tables + per-row slot indices
  instead of re-stacking reps every call (``CachePlan.device_resident``).
* ``profile`` — ``StageProfiler``: per-phase wall-clock taxonomy of the
  hot path (stage1/pack/dispatch/device/unpack, plus the loop-level
  queue_idle phase), threaded through the engine and surfaced by
  ``RankingService.stats()`` and the serve bench's breakdown rows. Its
  phases are fed by ``repro.obs.trace.span``, which also puts every span
  on the JAX profiler's clock as a ``serve.<name>`` annotation: an
  operator who captures a profile (``jax.profiler.start_server`` +
  XProf/Perfetto) sees the ``serve.*`` spans beside the device's ops.
* ``hedging`` — ``HedgePolicy`` (rolling-p99 decision) + ``HedgedRunner``
  (real duplicate execution of straggling chunks, first result wins).
* ``plan``    — ``ServePlan``: the frozen, validated, JSON-serializable
  serving configuration (nested Graph/Kernel/Batch/Shard/Cache sections,
  cross-field validation with a documented resolution table, named
  presets) — the config spine every entry point shares.
* ``service`` — ``RankingService``: multi-scenario router hosting several
  registry models behind one ``submit(scenario, request)`` API, with a
  shared rep-cache budget across scenario engines.
* ``reference`` — ``ReferenceScorer``: the plain float32 reference
  (un-rewritten graph, ``vani``, highest matmul precision) and the stated
  per-platform tolerance (``SCORE_TOL``) every cross-shape score check
  uses — differently shaped executables are not bit-identical.
* ``errors``  — the serving error taxonomy (``ServeError`` and its typed
  subclasses), stdlib-only so fault specs and recovery policies import
  without the JAX stack.

Fault tolerance rides the plan spine as well (``FaultPlan``, the ``ft``
section): deterministic fault injection at named sites
(``repro.ft.FaultInjector``), per-request retries with
deadline-budgeted backoff, a circuit breaker on stage-2 device-tier
dispatch that routes packs through the bit-identical re-stacking
fallback while open, device-tier quarantine on failed donated writes,
and batcher worker supervision — see ``serve/README.md`` § Failure
handling.

The memory hierarchy rides the plan spine as the ``mem`` section
(``MemPlan``, backed by ``repro.mem``): ``mem__cold_tier=True`` adds a
byte-budgeted host-RAM cold arena UNDER the hot LRU — evictions demote
into it instead of discarding, a hot miss with a cold hit serves from
one arena read (no stage-1 recompute, no device slot), an async worker
promotes only users touched ``promote_touches`` times within
``promote_window_s`` back to hot, and ``ServingEngine.warm`` /
``RankingService.warm`` bulk-precompute reps straight into the arena —
see ``serve/README.md`` § Memory hierarchy.

Observability rides the plan spine too (``ObsPlan``): ``obs__trace=True``
threads a ``repro.obs.Tracer`` through engine/batcher/cache (request and
group timelines, exported to Perfetto via ``repro.obs.export``), and
``obs__metrics`` (on by default) backs ``RankingService.stats()``'s
p50/p99 request-latency and queue-wait histograms.
"""
from repro.serve.batcher import (  # noqa: F401
    SLO_BEST_EFFORT,
    SLO_DEADLINE,
    CoalescingBatcher,
)
from repro.serve.cache import DeviceRepStore, UserRepCache  # noqa: F401
from repro.serve.errors import (  # noqa: F401
    AdmissionError,
    BatcherClosedError,
    CircuitOpenError,
    FaultInjected,
    RetryExhausted,
    ServeError,
    WorkerCrashedError,
)
from repro.serve.engine import (  # noqa: F401
    ServeRequest,
    ServeResult,
    ServingEngine,
)
from repro.serve.hedging import HedgedRunner, HedgePolicy  # noqa: F401
from repro.serve.profile import StageProfiler  # noqa: F401
from repro.serve.plan import (  # noqa: F401
    PRESETS,
    BatchPlan,
    CachePlan,
    FaultPlan,
    GraphPlan,
    KernelPlan,
    MemPlan,
    ObsPlan,
    PlanError,
    PlanResolutionWarning,
    ServePlan,
    ShardPlan,
)
from repro.serve.reference import (  # noqa: F401
    SCORE_TOL,
    ReferenceScorer,
)
from repro.serve.service import RankingService  # noqa: F401
