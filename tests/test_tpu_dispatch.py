"""The ``tpu`` preset launches stage 2 without waiting for the result.

It runs no hedge: every pack goes through the non-blocking launch, so the
host packs the next pack (and the next group) while the device computes,
and ``collect`` does the waiting. The scores are those of a hedging
engine, bit for bit: the same executable on the same inputs. Checked on a
small DIN with the preset's Pallas kernels (interpreted on the CPU).
"""
import jax
import numpy as np
import pytest

from repro.data.features import make_recsys_feeds
from repro.graph.executor import init_graph_params
from repro.models.recsys import build_din
from repro.serve import RankingService, ServePlan, ServeRequest, ServingEngine

HEDGE_KEYS = ("hedges_launched", "hedge_wins", "pool_exhausted")


@pytest.fixture(scope="module")
def din():
    graph, _ = build_din(embed_dim=8, seq_len=12, attn_mlp=(16, 8),
                         mlp=(24, 12), item_vocab=128)
    params = init_graph_params(graph, jax.random.PRNGKey(3))
    user_in = {n.name for n in graph.input_nodes()
               if n.attrs.get("domain") == "user"}
    return graph, params, user_in


def _request(graph, user_in, uid, n):
    feeds = make_recsys_feeds(graph, n, jax.random.PRNGKey(uid + 11))
    return ServeRequest(
        user_id=uid,
        user_feeds={k: v for k, v in feeds.items() if k in user_in},
        candidate_feeds={k: v for k, v in feeds.items()
                         if k not in user_in})


def _groups(graph, user_in):
    """Two groups of three users each, at the warm-up group's shapes."""
    return [[_request(graph, user_in, uid, n)
             for uid, n in zip(uids, (7, 19, 12))]
            for uids in ((0, 1, 2), (3, 4, 5))]


def _overlapped(eng, groups):
    """Both groups launched before either is collected; the handles and
    every request's result."""
    handles = [eng.begin_coalesced(g) for g in groups]
    return handles, [r for h in handles for r in eng.collect(h)]


def test_tpu_preset_runs_no_hedge():
    plan = ServePlan.preset("tpu")
    assert plan.batch.hedging is False
    # the preset is still the Pallas serving shape it was
    assert plan.kernel.use_pallas and plan.kernel.kernel_gather
    assert plan.graph.reparam_attention and plan.kernel.gather_attention
    # the paper's default keeps hedging
    assert ServePlan.preset("paper").batch.hedging


def test_tpu_preset_launches_every_pack_without_blocking(din):
    graph, params, user_in = din
    eng = ServingEngine(graph, params, plan=ServePlan.preset("tpu"))
    warm, *_ = _groups(graph, user_in)
    eng.score_coalesced([_request(graph, user_in, 100 + r.user_id, 10)
                         for r in warm])            # the shape's compile
    calls = eng.stage2_calls
    handles, _ = _overlapped(eng, _groups(graph, user_in))
    launched = [entry for h in handles for entry in h.launched]
    assert eng.stage2_calls - calls == len(launched) == 2
    assert [blocked for _, _, blocked in launched] == [False, False]
    assert [hedged for _, hedged, _ in launched] == [0, 0]
    assert eng.stage2_blocking_launches == 0
    assert eng.hedge_counters() == dict.fromkeys(HEDGE_KEYS)
    eng.close()


def test_tpu_preset_scores_equal_hedged_engine_bit_for_bit(din):
    graph, params, user_in = din
    groups = _groups(graph, user_in)
    eng = ServingEngine(graph, params, plan=ServePlan.preset("tpu"))
    hedging = ServingEngine(graph, params, plan=ServePlan.preset(
        "tpu").evolve(batch__hedging=True))
    _, got = _overlapped(eng, groups)
    handles, want = _overlapped(hedging, groups)
    assert len(got) == len(want) == 6
    for g, w in zip(got, want):
        assert np.array_equal(g.scores, w.scores)
    # the hedging engine waited on every launch, its compile call included
    assert all(blocked for h in handles for _, _, blocked in h.launched)
    assert hedging.stage2_blocking_launches == hedging.stage2_calls == 2
    assert eng.stage2_blocking_launches == 0
    eng.close()
    hedging.close()


@pytest.mark.parametrize("preset", ["tpu", "paper"])
def test_service_stats_report_blocking_launches_and_hedges(din, preset):
    graph, params, user_in = din
    svc = RankingService(preset)
    svc.register("din", graph=graph, params=params)
    for g in _groups(graph, user_in):
        for f in [svc.submit("din", r) for r in g]:
            f.result(timeout=300)
    sc = svc.stats()["scenarios"]["din"]
    metrics = sc["metrics"]
    assert sc["stage2_calls"] >= 1
    assert metrics["stage2_blocking_launches"] == \
        sc["stage2_blocking_launches"]
    if preset == "tpu":
        assert sc["stage2_blocking_launches"] == 0
        assert all(sc[k] is None for k in HEDGE_KEYS)
        assert not set(HEDGE_KEYS) & set(metrics)
    else:
        # every launch of a hedging engine waits for its result
        assert sc["stage2_blocking_launches"] == sc["stage2_calls"]
        for k in HEDGE_KEYS:
            assert isinstance(sc[k], int) and metrics[k] == sc[k]
        assert sc["hedge_wins"] <= sc["hedges_launched"]
    svc.close()
