"""Every preset launches stage 2 without waiting for the result.

A launch only enqueues the pack, so the host packs the next pack (and the
next group) while the device computes, and ``collect`` does the waiting
under the ``device`` phase, once a pack. Two groups in flight together
score exactly as each does alone: the same executable on the same
inputs. Checked on a small DIN; the ``tpu`` preset runs its Pallas
kernels (interpreted on the CPU).
"""
import jax
import numpy as np
import pytest

from repro.data.features import make_recsys_feeds
from repro.graph.executor import init_graph_params
from repro.models.recsys import build_din
from repro.serve import RankingService, ServePlan, ServeRequest, ServingEngine

# the one-process presets; ``distributed`` shards candidates over a mesh
PRESETS = ["paper", "vanilla", "uoi", "tpu"]


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
    each group's results."""
    handles = [eng.begin_coalesced(g) for g in groups]
    return handles, [eng.collect(h) for h in handles]


def test_tpu_preset_is_the_pallas_serving_shape():
    plan = ServePlan.preset("tpu")
    assert plan.kernel.use_pallas and plan.kernel.kernel_gather
    assert plan.graph.reparam_attention and plan.kernel.gather_attention
    assert plan == ServePlan(graph=plan.graph, kernel=plan.kernel)


@pytest.mark.parametrize("preset", PRESETS)
def test_every_pack_launches_without_blocking(din, preset):
    graph, params, user_in = din
    eng = ServingEngine(graph, params, plan=ServePlan.preset(preset))
    warm, *_ = _groups(graph, user_in)
    eng.score_coalesced([_request(graph, user_in, 100 + r.user_id, 10)
                         for r in warm])            # the shape's compile
    calls = eng.stage2_calls
    device = eng.profiler.snapshot()["device"]["calls"]
    handles, _ = _overlapped(eng, _groups(graph, user_in))
    launched = [out for h in handles for out in h.launched]
    assert all(isinstance(out, dict) for out in launched)
    assert eng.stage2_calls - calls == len(launched) >= 2
    # collect waited once for every pack: no launch waited for itself
    assert eng.profiler.snapshot()["device"]["calls"] - device \
        == len(launched)
    eng.close()


@pytest.mark.parametrize("preset", PRESETS)
def test_overlapped_groups_score_as_lockstep(din, preset):
    graph, params, user_in = din
    groups = _groups(graph, user_in)
    eng = ServingEngine(graph, params, plan=ServePlan.preset(preset))
    _, overlapped = _overlapped(eng, groups)
    for group, got in zip(groups, overlapped):
        want = eng.score_coalesced(group)
        assert len(got) == len(want) == len(group)
        for g, w in zip(got, want):
            assert np.array_equal(g.scores, w.scores)
    eng.close()


@pytest.mark.parametrize("preset", PRESETS)
def test_service_stats_carry_no_hedge_keys(din, preset):
    graph, params, user_in = din
    svc = RankingService(preset)
    svc.register("din", graph=graph, params=params)
    for g in _groups(graph, user_in):
        for f in [svc.submit("din", r) for r in g]:
            f.result(timeout=300)
    sc = svc.stats()["scenarios"]["din"]
    metrics = sc["metrics"]
    assert sc["stage2_calls"] >= 1
    assert metrics["stage2_calls"] == sc["stage2_calls"]
    for stats in (sc, metrics):
        assert not [k for k in stats if "hedge" in k or "blocking" in k]
    svc.close()
