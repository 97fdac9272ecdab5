"""``RankingService`` — a multi-scenario serving router.

Industrial rankers serve heterogeneous scenario models side by side
(per-stage rankers, per-surface models, A/B variants); the repo's
``configs/`` registry already carries several ranking scenarios (din,
deepfm, fm, dlrm-mlperf, paper-ranking) that could previously only be
served one-at-a-time through ad-hoc flags. ``RankingService`` hosts them
behind ONE ``submit(scenario, request)`` API:

* **per-scenario engines** — each registered scenario gets its own
  ``ServingEngine`` compiled from a ``ServePlan`` (the service default or a
  per-scenario override) and its own ``CoalescingBatcher`` (cross-user
  coalescing stays within a scenario: different graphs cannot share a
  stage-2 executable);
* **registry-by-name** — ``service.register("din")`` builds the scenario
  from ``repro.configs`` (``smoke_build`` by default, the full-size
  ``BUILD`` with ``smoke=False``) and initializes params from a fixed
  seed, so a registered scenario is bit-reproducible; callers may instead
  pass an explicit ``graph``/``params`` pair (e.g. trained weights);
* **shared rep-cache budget** — every scenario engine plugs into ONE
  bounded ``UserRepCache``: ``shared_cache_users`` caps the LIVE user
  representations across all scenarios together (one LRU, evictions
  compete globally), with cache keys namespaced per scenario so equal user
  ids from different scenarios can never collide on wrong-shaped reps.

Scores match a standalone per-scenario engine within the stated tolerance:
routing adds no numerics — the same plan builds the same executable
family, and the shared cache only changes *when* stage 1 recomputes, never
what stage 2 computes. Only the pack shapes differ, because the batcher
packs by arrival time (tested).

Usage::

    svc = RankingService(ServePlan.preset("paper"))
    svc.register("din"); svc.register("deepfm")
    fut = svc.submit("din", req)          # Future[ServeResult]
    res = svc.score("deepfm", req2)       # synchronous
    svc.close()
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import Future
from typing import Iterable, Mapping, Sequence

import jax

from repro.graph.ir import Graph
from repro.serve.batcher import SLO_BEST_EFFORT, CoalescingBatcher
from repro.serve.cache import UserRepCache
from repro.serve.engine import ServeRequest, ServeResult, ServingEngine
from repro.serve.plan import ServePlan


@dataclasses.dataclass
class _Scenario:
    name: str
    plan: ServePlan
    source_graph: Graph          # pre-rewrite graph (feed specs live here)
    source_params: dict          # its params, before any rewrite
    user_inputs: frozenset[str]  # input names with domain == "user"
    engine: ServingEngine
    batcher: CoalescingBatcher


class RankingService:
    """Host several scenario models behind one ``submit`` API.

    ``plan`` (a ``ServePlan`` or preset name) is the default serving shape
    for registered scenarios; ``shared_cache_users`` is the TOTAL live-user
    budget of the shared rep cache (defaults to the plan's
    ``max_cached_users``). ``smoke`` picks the registry build size used by
    name registration; ``seed`` the param-init key.
    """

    def __init__(self, plan: ServePlan | str | None = None, *,
                 smoke: bool = True, seed: int = 0,
                 shared_cache_users: int | None = None):
        if isinstance(plan, str):
            plan = ServePlan.preset(plan)
        self.plan = plan if plan is not None else ServePlan()
        self.smoke = smoke
        self.seed = seed
        budget = (shared_cache_users if shared_cache_users is not None
                  else self.plan.cache.max_cached_users)
        self.shared_cache = UserRepCache(max_users=budget)
        self._scenarios: dict[str, _Scenario] = {}
        self._closed = False

    # -- registration -------------------------------------------------------
    def register(self, scenario: str, *, graph: Graph | None = None,
                 params: dict | None = None,
                 plan: ServePlan | str | None = None,
                 smoke: bool | None = None,
                 seed: int | None = None) -> ServingEngine:
        """Register one scenario model and compile its engine.

        With no ``graph``, the scenario is built from the ``repro.configs``
        registry by name (``smoke_build``/``BUILD`` per ``smoke``) and
        params are initialized from ``seed`` — deterministic, so a
        standalone engine built the same way scores the same model.
        Returns the scenario's engine.
        """
        if self._closed:
            raise RuntimeError("service is closed")
        if scenario in self._scenarios:
            raise ValueError(f"scenario {scenario!r} is already registered")
        if (graph is None) != (params is None):
            raise ValueError("pass graph and params together (or neither, "
                             "to build from the configs registry)")
        if isinstance(plan, str):
            plan = ServePlan.preset(plan)
        plan = plan if plan is not None else self.plan
        if graph is None:
            from repro import configs as cfgreg
            from repro.graph.executor import init_graph_params
            mod = cfgreg.get_config(scenario)
            use_smoke = self.smoke if smoke is None else smoke
            build = mod.smoke_build() if use_smoke else mod.BUILD
            built = build()
            graph = built[0] if isinstance(built, tuple) else built
            params = init_graph_params(
                graph, jax.random.PRNGKey(self.seed if seed is None
                                          else seed))
        user_inputs = frozenset(n.name for n in graph.input_nodes()
                                if n.attrs.get("domain") == "user")
        engine = ServingEngine(graph, params, plan=plan,
                               cache=self.shared_cache,
                               cache_scope=scenario)
        # the whole batch section rides the plan spine: continuous loop,
        # in-flight budget, admission thresholds, and the ft section's
        # retry knobs included
        batcher = CoalescingBatcher.from_plan(engine, plan.batch, plan.ft)
        self._scenarios[scenario] = _Scenario(
            name=scenario, plan=plan, source_graph=graph,
            source_params=params, user_inputs=user_inputs, engine=engine,
            batcher=batcher)
        return engine

    # -- lookup -------------------------------------------------------------
    def _get(self, scenario: str) -> _Scenario:
        try:
            return self._scenarios[scenario]
        except KeyError:
            raise KeyError(
                f"scenario {scenario!r} is not registered; registered: "
                f"{sorted(self._scenarios)}") from None

    @property
    def scenarios(self) -> list[str]:
        return sorted(self._scenarios)

    def engine(self, scenario: str) -> ServingEngine:
        return self._get(scenario).engine

    def source_graph(self, scenario: str) -> Graph:
        """The scenario's pre-rewrite graph (input/feed specs)."""
        return self._get(scenario).source_graph

    def source_params(self, scenario: str) -> dict:
        """The scenario's params before any rewrite (what a reference
        scorer runs ``source_graph`` with)."""
        return self._get(scenario).source_params

    def split_feeds(self, scenario: str, feeds: Mapping[str, jax.Array]
                    ) -> tuple[dict, dict]:
        """Partition a flat feed dict into (user_feeds, candidate_feeds)
        per the scenario graph's ``domain`` coloring — the ``ServeRequest``
        contract."""
        user_in = self._get(scenario).user_inputs
        return ({k: v for k, v in feeds.items() if k in user_in},
                {k: v for k, v in feeds.items() if k not in user_in})

    # -- scoring ------------------------------------------------------------
    def submit(self, scenario: str, req: ServeRequest, *,
               slo: str = SLO_BEST_EFFORT,
               deadline_ms: float | None = None) -> "Future[ServeResult]":
        """Route one request to its scenario's batcher (non-blocking)."""
        return self._get(scenario).batcher.submit(req, slo=slo,
                                                  deadline_ms=deadline_ms)

    def score(self, scenario: str, req: ServeRequest) -> ServeResult:
        return self.submit(scenario, req).result()

    def score_many(self, items: Sequence[tuple[str, ServeRequest]]
                   ) -> list[ServeResult]:
        """Score an interleaved multi-scenario stream: submit everything
        (scenario batchers coalesce their own co-arrivals concurrently),
        then collect results in submission order."""
        futs = [self.submit(scenario, req) for scenario, req in items]
        return [f.result() for f in futs]

    # -- memory hierarchy ----------------------------------------------------
    def warm(self, scenario: str, items, feature_version: int = 0) -> int:
        """Bulk-precompute stage-1 reps into a scenario's cold tier (see
        ``ServingEngine.warm``); requires ``plan.mem.cold_tier=True`` for
        that scenario. ``items``: ``(user_id, user_feeds)`` pairs."""
        return self._get(scenario).engine.warm(
            items, feature_version=feature_version)

    # -- observability ------------------------------------------------------
    def stats(self) -> dict:
        """Per-scenario serving counters (including the stage-boundary
        profile and the device rep tier, when live) + the shared cache's
        state with byte accounting — ``shared_cache.boundary_bytes`` is
        the number to read when sizing ``CachePlan.device_slots``."""
        return {
            "scenarios": {
                s.name: {
                    "preset": s.plan.preset_name(),
                    "mode": s.engine.mode,
                    "two_stage": s.engine.two_stage,
                    "device_resident": s.engine.device_resident,
                    "requests": s.batcher.requests,
                    "batches": s.batcher.batches,
                    "coalesced_requests": s.batcher.coalesced_requests,
                    "queue_wait_ms": s.batcher.queue_wait_ms,
                    "shed_requests": s.batcher.shed_requests,
                    "shed_best_effort": s.batcher.shed_best_effort,
                    "shed_deadline": s.batcher.shed_deadline,
                    "degraded_requests": s.batcher.degraded_requests,
                    # self-healing counters: retries/respawns on the
                    # batcher, breaker + injector state on the engine —
                    # the chaos harness asserts recovery through these
                    "retries_attempted": s.batcher.retries_attempted,
                    "retries_exhausted": s.batcher.retries_exhausted,
                    "worker_crashes": s.batcher.worker_crashes,
                    "worker_respawns": s.batcher.worker_respawns,
                    "fallback_packs": getattr(s.engine, "fallback_packs", 0),
                    "corruptions_detected": getattr(
                        s.engine, "corruptions_detected", 0),
                    "breaker": (s.engine.breaker.stats()
                                if getattr(s.engine, "breaker", None)
                                is not None else None),
                    "faults": (s.engine.fault_injector.stats()
                               if getattr(s.engine, "fault_injector", None)
                               is not None else None),
                    "stage1_calls": s.engine.stage1_calls,
                    "stage2_calls": s.engine.stage2_calls,
                    # candidate + user-index bytes handed to the device,
                    # padding rows included
                    "h2d_bytes": s.engine.h2d_bytes,
                    "pipeline_forks": s.engine.pipeline_forks,
                    # log-bucketed distributions (repro.obs): the tail
                    # numbers an SLO is judged on, which the cumulative
                    # totals above cannot show
                    "latency": {
                        "request_ms": s.batcher.request_latency.snapshot(),
                        "queue_wait_ms": s.batcher.queue_wait.snapshot(),
                    },
                    # unified counter+histogram snapshot when the
                    # engine's registry is on (plan.obs.metrics)
                    "metrics": (s.engine.metrics.snapshot()
                                if s.engine.metrics is not None else None),
                    "profile": s.engine.profiler.snapshot(),
                    "device_store": (s.engine.device_store.stats()
                                     if s.engine.device_store is not None
                                     else None),
                    # memory hierarchy (plan.mem): cold arena occupancy,
                    # promotion-policy counters, warm-feed totals
                    "mem": s.engine.mem_stats(),
                } for s in self._scenarios.values()},
            # host-tier stats() carries users/max_users/hits/misses/
            # evictions plus bytes + per-boundary bytes
            "shared_cache": self.shared_cache.stats(),
        }

    # -- lifecycle ----------------------------------------------------------
    def close(self) -> None:
        for s in self._scenarios.values():
            s.batcher.close()
            s.engine.close()
        self._closed = True

    def __enter__(self) -> "RankingService":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __contains__(self, scenario: str) -> bool:
        return scenario in self._scenarios

    def __iter__(self) -> Iterable[str]:
        return iter(self.scenarios)
