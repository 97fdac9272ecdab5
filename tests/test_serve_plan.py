"""ServePlan (the declarative serving config spine) + RankingService.

Covers: JSON round-trip, preset equality, frozen-ness, the documented
resolution table (reject vs auto-resolve, including through the legacy
kwargs shim), bit-identical scores between legacy-kwargs engines and the
equivalent plan-built engines across vani/uoi/mari, and the multi-scenario
RankingService router (interleaved requests scored like standalone
per-scenario engines, within the stated tolerance, shared rep-cache budget with scenario-scoped keys).
"""
import dataclasses
import json
import warnings

import jax
import numpy as np
import pytest

from repro.data.features import make_recsys_feeds
from repro.graph.executor import init_graph_params
from repro.models.recsys import build_din
from repro.serve import (PRESETS, BatchPlan, CachePlan, GraphPlan,
                         KernelPlan, PlanError, PlanResolutionWarning,
                         SCORE_TOL, RankingService, ServePlan,
                         ServeRequest, ServingEngine, ShardPlan)

SCENARIOS = ("din", "deepfm", "fm")


@pytest.fixture(scope="module")
def din_problem():
    graph, _ = build_din(embed_dim=8, seq_len=12, attn_mlp=(16, 8),
                         mlp=(24, 12), item_vocab=128)
    params = init_graph_params(graph, jax.random.PRNGKey(0))
    user_in = {n.name for n in graph.input_nodes()
               if n.attrs.get("domain") == "user"}
    return graph, params, user_in


def _request(graph, user_in, uid, n, seed, version=0):
    feeds = make_recsys_feeds(graph, n, jax.random.PRNGKey(seed))
    return ServeRequest(
        user_id=uid,
        user_feeds={k: v for k, v in feeds.items() if k in user_in},
        candidate_feeds={k: v for k, v in feeds.items() if k not in user_in},
        feature_version=version)


class TestServePlanBasics:
    def test_json_round_trip_all_presets(self):
        for name, plan in PRESETS.items():
            rt = ServePlan.from_json(plan.to_json())
            assert rt == plan, name
            assert rt.preset_name() == name

    def test_round_trip_of_nondefault_plan(self):
        plan = ServePlan(
            graph=GraphPlan(mode="uoi", two_stage=True),
            batch=BatchPlan(max_batch=256, min_bucket=32, hedging=False,
                            linger_ms=7.5),
            shard=ShardPlan(shard_candidates=2),
            cache=CachePlan(max_cached_users=100))
        rt = ServePlan.from_json(plan.to_json())
        assert rt == plan
        assert rt.shard.shard_candidates == 2      # int survives, not bool
        assert rt.preset_name() is None

    def test_preset_equality_and_identity(self):
        assert ServePlan.preset("paper") == ServePlan()
        assert ServePlan.preset("vanilla").graph.mode == "vani"
        assert ServePlan.preset("tpu").kernel.use_pallas
        assert ServePlan.preset("distributed").shard.shard_candidates
        # distributed preset must be SPMD-safe out of the box
        assert not ServePlan.preset("distributed").batch.hedging
        with pytest.raises(PlanError, match="unknown preset"):
            ServePlan.preset("bogus")

    def test_frozen(self):
        plan = ServePlan()
        with pytest.raises(dataclasses.FrozenInstanceError):
            plan.graph = GraphPlan(mode="uoi")
        with pytest.raises(dataclasses.FrozenInstanceError):
            plan.graph.mode = "uoi"

    def test_evolve(self):
        plan = ServePlan().evolve(graph__mode="uoi", batch__max_batch=64)
        assert plan.graph.mode == "uoi" and plan.batch.max_batch == 64
        # untouched sections are shared (frozen => safe) and equal
        assert plan.kernel == ServePlan().kernel
        with pytest.raises(TypeError):
            plan.evolve(nosection__x=1)
        with pytest.raises(TypeError):
            plan.evolve(graph__nofield=1)
        with pytest.raises(TypeError):
            plan.evolve(mode="uoi")                # missing section prefix

    def test_from_dict_rejects_unknown_sections_and_fields(self):
        with pytest.raises(PlanError, match="unknown plan sections"):
            ServePlan.from_dict({"graphs": {}})
        with pytest.raises(PlanError, match="unknown graph-plan fields"):
            ServePlan.from_dict({"graph": {"mde": "mari"}})

    def test_malformed_sections_raise_plan_error(self):
        """A hand-edited plan file with a null/scalar section must fail
        with the documented PlanError, not a bare TypeError."""
        for bad in ('{"graph": null}', '{"graph": "mari"}', '"mari"'):
            with pytest.raises(PlanError):
                ServePlan.from_json(bad)

    def test_wrong_typed_scalars_raise_plan_error(self):
        """Quoted numbers / stringy booleans in a plan file fail with the
        documented PlanError naming the field, not a bare TypeError."""
        for bad, field in ((' {"batch": {"max_batch": "64"}}', "max_batch"),
                           ('{"graph": {"mode": 3}}', "mode"),
                           ('{"kernel": {"use_pallas": "yes"}}',
                            "use_pallas"),
                           ('{"batch": {"max_batch": true}}', "max_batch"),
                           ('{"cache": {"max_cached_users": "10"}}',
                            "max_cached_users")):
            with pytest.raises(PlanError, match=field):
                ServePlan.from_json(bad)

    def test_sections_accept_dicts(self):
        plan = ServePlan(graph={"mode": "uoi"}, batch={"max_batch": 32})
        assert plan.graph.mode == "uoi"
        assert plan.batch.max_batch == 32 and plan.batch.min_bucket == 32

    def test_save_load(self, tmp_path):
        p = tmp_path / "plan.json"
        plan = ServePlan.preset("tpu")
        plan.save(str(p))
        assert ServePlan.load(str(p)) == plan

    def test_dist_runner_plan_file_fields_survive(self, tmp_path):
        """The SPMD runner layers only its operating requirements (sharding
        on, hedging off) on a --plan file — the file's max_batch/min_bucket/
        compress_scores must survive unless flags explicitly override."""
        import argparse
        from repro.dist.runner import build_plan
        path = tmp_path / "plan.json"
        ServePlan(batch=BatchPlan(max_batch=1024, min_bucket=64),
                  shard=ShardPlan(shard_candidates=True,
                                  compress_scores=True)).save(str(path))
        ns = lambda **kw: argparse.Namespace(
            **{"plan": str(path), "max_batch": None, "min_bucket": None,
               "compress_scores": False, **kw})
        plan = build_plan(ns())
        assert plan.batch.max_batch == 1024
        assert plan.batch.min_bucket == 64
        assert plan.shard.compress_scores          # file value survives
        assert plan.shard.shard_candidates and not plan.batch.hedging
        # an explicit shard COUNT in the file survives the forced-on rule
        path2 = tmp_path / "plan2.json"
        ServePlan(shard=ShardPlan(shard_candidates=2)).save(str(path2))
        assert build_plan(ns(plan=str(path2))).shard.shard_candidates == 2
        # explicit flag beats the file
        assert build_plan(ns(max_batch=128)).batch.max_batch == 128
        # no file: the runner's own defaults
        bare = build_plan(argparse.Namespace(
            plan=None, max_batch=None, min_bucket=None,
            compress_scores=False))
        assert bare.batch.max_batch == 256 and bare.batch.min_bucket == 16


class TestResolutionTable:
    """Every previously-silent invalid combo now rejects or auto-resolves
    per the documented table — at plan construction, not deep inside the
    engine."""

    def test_unknown_mode_rejected(self):
        with pytest.raises(PlanError, match="unknown mode"):
            ServePlan(graph=GraphPlan(mode="bogus"))

    def test_compress_scores_requires_shard_candidates(self):
        with pytest.raises(PlanError, match="shard_candidates"):
            ServePlan(shard=ShardPlan(compress_scores=True))

    def test_two_stage_vani_rejected(self):
        with pytest.raises(PlanError, match="user-only stage"):
            ServePlan(graph=GraphPlan(mode="vani", two_stage=True))

    @pytest.mark.parametrize("section,field,value", [
        ("batch", "max_batch", 0),
        ("batch", "min_bucket", 0),
        ("batch", "max_users_per_batch", 0),
        ("batch", "max_coalesce", 0),
        ("batch", "linger_ms", -1.0),
        ("batch", "deadline_linger_frac", 1.5),
        ("cache", "max_cached_users", 0),
        ("shard", "shard_candidates", -2),
    ])
    def test_bad_scalars_rejected(self, section, field, value):
        with pytest.raises(PlanError):
            ServePlan(**{section: {field: value}})

    def test_kernel_gather_without_pallas_resolves(self):
        with pytest.warns(PlanResolutionWarning, match="kernel_gather"):
            plan = ServePlan(kernel=KernelPlan(kernel_gather=True))
        assert not plan.kernel.kernel_gather
        assert plan.resolution_notes

    def test_gather_attention_without_decomposed_attention_resolves(self):
        # vani: no decomposed attention at all
        with pytest.warns(PlanResolutionWarning, match="gather_attention"):
            plan = ServePlan(graph=GraphPlan(mode="vani"),
                             kernel=KernelPlan(gather_attention=True))
        assert not plan.kernel.gather_attention
        # mari without reparam_attention: still nothing to gather from
        with pytest.warns(PlanResolutionWarning, match="gather_attention"):
            plan = ServePlan(kernel=KernelPlan(gather_attention=True))
        assert not plan.kernel.gather_attention
        # the VALID combo stays untouched (and silent)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            plan = ServePlan(graph=GraphPlan(reparam_attention=True),
                             kernel=KernelPlan(gather_attention=True))
        assert plan.kernel.gather_attention

    def test_rewrite_knobs_outside_mari_resolve(self):
        with pytest.warns(PlanResolutionWarning, match="MaRI rewrite"):
            plan = ServePlan(graph=GraphPlan(mode="uoi",
                                             reparam_attention=True,
                                             fragment=True))
        assert not plan.graph.reparam_attention
        assert not plan.graph.fragment

    def test_min_bucket_clamped_silently(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error")       # normalization, no warning
            plan = ServePlan(batch=BatchPlan(max_batch=16))
        assert plan.batch.min_bucket == 16

    def test_resolution_is_idempotent_through_json(self):
        with pytest.warns(PlanResolutionWarning):
            plan = ServePlan(kernel=KernelPlan(kernel_gather=True,
                                               gather_attention=True))
        with warnings.catch_warnings():
            warnings.simplefilter("error")       # resolved plan is valid
            rt = ServePlan.from_json(plan.to_json())
        assert rt == plan


class TestAdmissionPlanFields:
    """The continuous-loop/admission knobs ride the ServePlan spine:
    validated scalars, documented resolutions, JSON round-trip — not
    ad-hoc kwargs."""

    def test_defaults(self):
        b = BatchPlan()
        assert b.continuous is True and b.max_inflight == 2
        assert b.admission is False
        assert b.shed_queue_depth is None and b.degrade_queue_depth is None
        assert b.degrade_frac == 0.5 and b.deadline_headroom_ms == 0.0

    @pytest.mark.parametrize("field,value", [
        ("max_inflight", 0),
        ("shed_queue_depth", 0),
        ("degrade_queue_depth", -1),
        ("degrade_frac", 0.0),
        ("degrade_frac", 1.5),
        ("deadline_headroom_ms", -1.0),
    ])
    def test_bad_scalars_rejected(self, field, value):
        with pytest.raises(PlanError):
            ServePlan(batch=BatchPlan(admission=True, **{field: value}))

    def test_degrade_above_shed_rejected(self):
        with pytest.raises(PlanError, match="degrade"):
            ServePlan(batch=BatchPlan(admission=True, shed_queue_depth=8,
                                      degrade_queue_depth=16))
        # the legal ordering (degrade engages at or before shed) is silent
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            ServePlan(batch=BatchPlan(admission=True, shed_queue_depth=16,
                                      degrade_queue_depth=8))

    def test_thresholds_without_admission_resolve(self):
        with pytest.warns(PlanResolutionWarning, match="admission"):
            plan = ServePlan(batch=BatchPlan(shed_queue_depth=8,
                                             deadline_headroom_ms=2.0))
        assert plan.batch.shed_queue_depth is None
        assert plan.batch.deadline_headroom_ms == 0.0
        assert plan.resolution_notes

    def test_json_round_trip(self):
        plan = ServePlan().evolve(batch__continuous=False,
                                  batch__max_inflight=4,
                                  batch__admission=True,
                                  batch__shed_queue_depth=64,
                                  batch__degrade_queue_depth=32,
                                  batch__degrade_frac=0.25,
                                  batch__deadline_headroom_ms=3.0)
        rt = ServePlan.from_json(plan.to_json())
        assert rt == plan
        assert rt.batch.continuous is False and rt.batch.max_inflight == 4
        assert rt.batch.shed_queue_depth == 64
        assert rt.batch.degrade_queue_depth == 32

    def test_type_table_covers_new_fields(self):
        with pytest.raises(PlanError, match="max_inflight"):
            ServePlan(batch={"max_inflight": "2"})
        with pytest.raises(PlanError, match="shed_queue_depth"):
            ServePlan(batch={"shed_queue_depth": 1.5})
        with pytest.raises(PlanError, match="continuous"):
            ServePlan(batch={"continuous": 1})

    def test_from_plan_wires_batcher(self, din_problem):
        """CoalescingBatcher.from_plan carries every batch-section knob."""
        from repro.serve import CoalescingBatcher
        graph, params, _ = din_problem
        plan = ServePlan().evolve(batch__hedging=False,
                                  batch__continuous=False,
                                  batch__max_inflight=3,
                                  batch__admission=True,
                                  batch__shed_queue_depth=9,
                                  batch__degrade_queue_depth=4,
                                  batch__degrade_frac=0.75,
                                  batch__deadline_headroom_ms=1.5,
                                  batch__linger_ms=7.0)
        eng = ServingEngine(graph, params, plan=plan)
        b = CoalescingBatcher.from_plan(eng, plan.batch, auto_start=False)
        assert (b.continuous, b.max_inflight, b.admission) == (False, 3,
                                                               True)
        assert b.shed_queue_depth == 9 and b.degrade_queue_depth == 4
        assert b.degrade_frac == 0.75 and b.deadline_headroom_ms == 1.5
        assert b.linger_ms == 7.0


class TestDeviceResidentPlan:
    """The ``CachePlan.device_resident`` knob follows the same spine rules
    as every other plan field: validated scalars, documented resolutions,
    idempotent through JSON."""

    def test_bad_device_slots_rejected(self):
        with pytest.raises(PlanError, match="device_slots"):
            ServePlan(cache=CachePlan(device_resident=True, device_slots=0))

    def test_device_resident_without_cache_resolves_off(self):
        with pytest.warns(PlanResolutionWarning, match="device_resident"):
            plan = ServePlan(cache=CachePlan(cache_user_reps=False,
                                             device_resident=True))
        assert not plan.cache.device_resident
        assert plan.resolution_notes

    def test_device_resident_drops_hedging(self):
        # BatchPlan defaults hedging=True; the device tier wins (hedged
        # duplicates would replay donated dispatches)
        with pytest.warns(PlanResolutionWarning, match="hedging"):
            plan = ServePlan(cache=CachePlan(device_resident=True))
        assert plan.cache.device_resident
        assert not plan.batch.hedging

    def test_device_slots_without_device_resident_dropped(self):
        with pytest.warns(PlanResolutionWarning, match="device_slots"):
            plan = ServePlan(cache=CachePlan(device_slots=8))
        assert plan.cache.device_slots is None

    def test_valid_combo_silent_and_roundtrips(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            plan = ServePlan(batch=BatchPlan(hedging=False),
                             cache=CachePlan(device_resident=True,
                                             device_slots=32))
            rt = ServePlan.from_json(plan.to_json())
        assert rt == plan
        assert rt.cache.device_resident and rt.cache.device_slots == 32


class TestLegacyShim:
    """ServingEngine(**kwargs) still works: it builds the equivalent plan,
    emits a DeprecationWarning, and fails fast on the combos that used to
    no-op silently."""

    def test_legacy_kwargs_deprecation_warning(self, din_problem):
        graph, params, _ = din_problem
        with pytest.warns(DeprecationWarning, match="ServePlan"):
            eng = ServingEngine(graph, params, mode="uoi", max_batch=32,
                                hedging=False)
        assert eng.plan == ServePlan(graph=GraphPlan(mode="uoi"),
                                     batch=BatchPlan(max_batch=32,
                                                     hedging=False))
        eng.close()

    def test_plan_path_does_not_warn(self, din_problem):
        graph, params, _ = din_problem
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            eng = ServingEngine(graph, params,
                                plan=ServePlan().evolve(batch__max_batch=32))
            eng.close()
            # no kwargs at all is the default plan, also not deprecated
            eng = ServingEngine(graph, params)
            eng.close()

    def test_plan_and_kwargs_mutually_exclusive(self, din_problem):
        graph, params, _ = din_problem
        with pytest.raises(TypeError, match="not both"):
            ServingEngine(graph, params, plan=ServePlan(), mode="mari")

    def test_unknown_kwarg_rejected(self, din_problem):
        graph, params, _ = din_problem
        with pytest.raises(TypeError, match="unknown ServingEngine kwargs"):
            ServingEngine(graph, params, mod="mari")

    def test_preset_name_accepted_as_plan(self, din_problem):
        graph, params, _ = din_problem
        eng = ServingEngine(graph, params, plan="vanilla")
        assert eng.mode == "vani" and not eng.two_stage
        eng.close()

    # satellite: the previously-silent no-op combos, through the shim
    def test_shim_kernel_gather_without_pallas_warns(self, din_problem):
        graph, params, _ = din_problem
        with pytest.warns(PlanResolutionWarning, match="kernel_gather"):
            eng = ServingEngine(graph, params, kernel_gather=True,
                                hedging=False)
        assert not eng.kernel_gather
        eng.close()

    def test_shim_gather_attention_vani_warns(self, din_problem):
        graph, params, _ = din_problem
        with pytest.warns(PlanResolutionWarning, match="gather_attention"):
            eng = ServingEngine(graph, params, mode="vani",
                                gather_attention=True, hedging=False)
        assert not eng.gather_attention
        eng.close()

    def test_shim_compress_scores_without_shard_raises(self, din_problem):
        graph, params, _ = din_problem
        with pytest.raises(ValueError, match="shard_candidates"):
            ServingEngine(graph, params, compress_scores=True)

    @pytest.mark.parametrize("mode", ["vani", "uoi", "mari"])
    def test_legacy_vs_plan_engines_bit_identical(self, din_problem, mode):
        """The shim builds the SAME engine the plan path builds — scores
        are bit-identical across all three paradigms."""
        graph, params, user_in = din_problem
        reqs = [_request(graph, user_in, 0, 9, seed=1),
                _request(graph, user_in, 1, 21, seed=2)]
        with pytest.warns(DeprecationWarning):
            legacy = ServingEngine(graph, params, mode=mode, max_batch=32,
                                   min_bucket=8, hedging=False)
        plan_eng = ServingEngine(graph, params, plan=ServePlan().evolve(
            graph__mode=mode, batch__max_batch=32, batch__min_bucket=8,
            batch__hedging=False))
        assert legacy.plan == plan_eng.plan
        for a, b in zip(legacy.score_coalesced(reqs),
                        plan_eng.score_coalesced(reqs)):
            np.testing.assert_array_equal(a.scores, b.scores)
        legacy.close()
        plan_eng.close()


class TestRankingService:
    """The multi-scenario router: per-scenario engines behind one
    submit(scenario, request) API, shared rep-cache budget."""

    @pytest.fixture(scope="class")
    def svc_plan(self):
        return ServePlan().evolve(batch__max_batch=64, batch__min_bucket=16,
                                  batch__hedging=False,
                                  batch__linger_ms=20.0,
                                  batch__max_coalesce=4)

    def _interleaved(self, svc, n=9):
        """Round-robin requests across scenarios; SAME user ids in every
        scenario on purpose — proves scenario-scoped cache keys."""
        items = []
        for r in range(n):
            sc = SCENARIOS[r % len(SCENARIOS)]
            feeds = make_recsys_feeds(svc.source_graph(sc), 7 + r,
                                      jax.random.PRNGKey(100 + r))
            uf, cf = svc.split_feeds(sc, feeds)
            items.append((sc, ServeRequest(user_id=r % 2, user_feeds=uf,
                                           candidate_feeds=cf)))
        return items

    def test_three_scenarios_bit_identical_to_standalone(self, svc_plan):
        """THE acceptance-criteria test: a service hosting din/deepfm/fm
        smoke builds scores an interleaved stream; per-scenario results
        match standalone per-scenario engines built the same way from the
        registry, within the stated CPU tolerance: the service's batcher
        packs requests by arrival time, so its executables' shapes differ
        from the standalone engine's U=1 calls."""
        from repro import configs as cfgreg
        atol, rtol = SCORE_TOL["cpu"]
        with RankingService(svc_plan, smoke=True, seed=0) as svc:
            for sc in SCENARIOS:
                svc.register(sc)
            assert svc.scenarios == sorted(SCENARIOS)
            items = self._interleaved(svc)
            results = svc.score_many(items)
            for sc in SCENARIOS:
                graph = cfgreg.get_config(sc).smoke_build()()[0]
                params = init_graph_params(graph, jax.random.PRNGKey(0))
                ref = ServingEngine(graph, params, plan=svc_plan)
                for (s, req), res in zip(items, results):
                    if s != sc:
                        continue
                    np.testing.assert_allclose(
                        res.scores, ref.score(req).scores, atol=atol,
                        rtol=rtol,
                        err_msg=f"{sc} diverged from standalone engine")
                ref.close()
            stats = svc.stats()
            assert set(stats["scenarios"]) == set(SCENARIOS)
            # interleaving actually exercised every scenario's engine
            assert all(v["stage2_calls"] >= 1
                       for v in stats["scenarios"].values())

    def test_shared_cache_is_scoped_per_scenario(self, svc_plan):
        with RankingService(svc_plan, shared_cache_users=16) as svc:
            for sc in SCENARIOS:
                svc.register(sc)
            svc.score_many(self._interleaved(svc, n=6))
            keys = svc.shared_cache.keys()
            # same raw user ids across scenarios live as DISTINCT entries
            scopes = {uid[0] for uid, _ in keys}
            assert scopes == set(SCENARIOS)
            assert len(keys) == 6                 # 3 scenarios x 2 users
            # scoped invalidation only touches the named scenario
            svc.engine("din").invalidate_user(0)
            assert len(svc.shared_cache) == 5
            assert ("din", 0) not in {uid for uid, _ in
                                      svc.shared_cache.keys()}

    def test_shared_budget_evicts_across_scenarios(self, svc_plan):
        """ONE LRU budget spans all scenarios: capping it below the live
        user count forces cross-scenario evictions."""
        with RankingService(svc_plan, shared_cache_users=2) as svc:
            for sc in SCENARIOS:
                svc.register(sc)
            svc.score_many(self._interleaved(svc, n=6))   # 6 scoped users
            assert len(svc.shared_cache) == 2
            assert svc.shared_cache.evictions >= 4

    def test_register_validation(self, svc_plan):
        with RankingService(svc_plan) as svc:
            svc.register("din")
            with pytest.raises(ValueError, match="already registered"):
                svc.register("din")
            with pytest.raises(KeyError, match="not registered"):
                svc.score("deepfm", None)
            with pytest.raises(ValueError, match="together"):
                svc.register("fm", graph=object())
            assert "din" in svc and "deepfm" not in svc

    def test_per_scenario_plan_override(self, svc_plan):
        """A scenario may carry its own plan (e.g. a vanilla baseline next
        to the paper engine) — the service still routes correctly."""
        with RankingService(svc_plan, smoke=True) as svc:
            svc.register("din")
            svc.register("fm", plan=svc_plan.evolve(graph__mode="vani"))
            assert svc.engine("din").mode == "mari"
            assert svc.engine("fm").mode == "vani"
            items = [(sc, self._req_for(svc, sc, seed))
                     for seed, sc in enumerate(("din", "fm", "din", "fm"))]
            results = svc.score_many(items)
            assert all(r.scores.shape[0] > 0 for r in results)

    def _req_for(self, svc, sc, seed):
        feeds = make_recsys_feeds(svc.source_graph(sc), 5 + seed,
                                  jax.random.PRNGKey(seed))
        uf, cf = svc.split_feeds(sc, feeds)
        return ServeRequest(user_id=seed, user_feeds=uf, candidate_feeds=cf)

    def test_stats_expose_profile_and_device_store(self, svc_plan):
        """Observability contract of this subsystem: per-scenario stats
        carry the stage-boundary profile, queue wait, the device-tier
        counters, and the shared cache's byte accounting."""
        plan = svc_plan.evolve(cache__device_resident=True)
        with RankingService(plan, smoke=True, seed=0) as svc:
            svc.register("din")
            svc.score("din", self._req_for(svc, "din", seed=3))
            st = svc.stats()
            sc = st["scenarios"]["din"]
            assert sc["device_resident"] is True
            prof = sc["profile"]
            assert set(prof) == {"stage1", "pack", "dispatch", "device",
                                 "unpack", "queue_idle"}
            assert prof["pack"]["calls"] >= 1
            assert prof["pack"]["total_ms"] >= 0.0
            ds = sc["device_store"]
            assert ds["resident"] == 1 and ds["writes"] == 1
            assert ds["bytes"] > 0
            assert set(ds["boundary_bytes"]) == set(
                svc.engine("din").split.boundary)
            assert sc["queue_wait_ms"] >= 0.0
            # host-tier byte accounting mirrors the same boundary names
            cache_stats = st["shared_cache"]
            assert cache_stats["bytes"] > 0
            assert set(cache_stats["boundary_bytes"]) == set(
                svc.engine("din").split.boundary)
