"""DIN's local activation unit pools by Eq. (3) of arXiv:1706.06978: the
unit's outputs are the behaviours' weights themselves, with no softmax, so
they need not sum to 1, and a masked slot weighs 0. Checked against a
hand-written numpy unit, then through the served path: the decomposed unit
contracted against stacked per-user tables (the gather-einsum kernel,
interpreted on the CPU) agrees with the un-rewritten graph on a coalesced
batch of three users."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.features import make_recsys_feeds
from repro.graph.executor import init_graph_params
from repro.models.recsys import build_din
from repro.nn.attention import target_attention
from repro.nn.layers import dense_apply
from repro.serve import ServePlan, ServeRequest, ServingEngine
from repro.serve.reference import SCORE_TOL, ReferenceScorer

B, L, D, H1, H2 = 3, 5, 4, 6, 3


@pytest.fixture(scope="module")
def unit():
    rng = np.random.default_rng(7)
    q = rng.normal(size=(B, D)).astype(np.float32)
    keys = rng.normal(size=(1, L, D)).astype(np.float32)
    mask = np.ones((1, L), bool)
    mask[0, 2] = False
    layers = {}
    for li, (i, o) in enumerate(((4 * D, H1), (H1, H2), (H2, 1))):
        layers[f"layer_{li}"] = {
            "w": (rng.normal(size=(i, o)) * 0.5).astype(np.float32),
            "b": (rng.normal(size=(o,)) * 0.1 + 0.2).astype(np.float32)}
    return q, keys, mask, layers


def _numpy_weights(q, keys, layers):
    """The activation unit's output a(e_j, v_A) for every candidate and
    behaviour, one at a time."""
    n = len(layers)
    a = np.zeros((q.shape[0], keys.shape[1]), np.float64)
    for b in range(q.shape[0]):
        for j in range(keys.shape[1]):
            k = keys[0, j].astype(np.float64)
            x = np.concatenate([k, q[b], k - q[b], k * q[b]])
            for li in range(n):
                p = layers[f"layer_{li}"]
                x = x @ p["w"] + p["b"]
                if li < n - 1:
                    x = np.maximum(x, 0.0)
            a[b, j] = x[0]
    return a


def _mlp(layers):
    def apply(x):
        for li in range(len(layers)):
            x = dense_apply(layers[f"layer_{li}"], x)
            if li < len(layers) - 1:
                x = jax.nn.relu(x)
        return x
    return apply


def test_target_attention_pools_by_the_units_unnormalized_outputs(unit):
    q, keys, mask, layers = unit
    with jax.default_matmul_precision("highest"):
        got = np.asarray(target_attention(jnp.asarray(q), jnp.asarray(keys),
                                          jnp.asarray(mask), _mlp(layers)))
    a = _numpy_weights(q, keys, layers) * mask
    want = a @ keys[0].astype(np.float64)                 # Eq. (3)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # the weights are the unit's raw outputs: no row sums to 1
    assert np.all(np.abs(a.sum(-1) - 1.0) > 0.05), a.sum(-1)


def test_a_masked_slot_contributes_exactly_zero(unit):
    q, keys, mask, layers = unit
    other = keys.copy()
    other[0, 2] = 1e6                        # only the masked slot moves
    run = jax.jit(lambda k: target_attention(
        jnp.asarray(q), k, jnp.asarray(mask), _mlp(layers)))
    np.testing.assert_array_equal(np.asarray(run(jnp.asarray(keys))),
                                  np.asarray(run(jnp.asarray(other))))
    # ... and pooling the four unmasked slots alone gives the same vector
    keep = np.flatnonzero(mask[0])
    with jax.default_matmul_precision("highest"):
        alone = target_attention(jnp.asarray(q), jnp.asarray(keys[:, keep]),
                                 jnp.ones((1, keep.size), bool),
                                 _mlp(layers))
        full = run(jnp.asarray(keys))
    np.testing.assert_allclose(np.asarray(full), np.asarray(alone),
                               rtol=1e-5, atol=1e-5)


def test_gather_einsum_path_agrees_with_vani_on_three_coalesced_users():
    graph, _ = build_din(embed_dim=8, seq_len=12, attn_mlp=(16, 8),
                         mlp=(24, 12), item_vocab=128)
    params = init_graph_params(graph, jax.random.PRNGKey(3))
    user_in = {n.name for n in graph.input_nodes()
               if n.attrs.get("domain") == "user"}

    def request(uid, n):
        feeds = make_recsys_feeds(graph, n, jax.random.PRNGKey(uid + 11))
        return ServeRequest(
            user_id=uid,
            user_feeds={k: v for k, v in feeds.items() if k in user_in},
            candidate_feeds={k: v for k, v in feeds.items()
                             if k not in user_in})

    reqs = [request(u, n) for u, n in ((0, 7), (1, 19), (2, 12))]
    # the tpu preset's path: MaRI with the decomposed unit, its per-user
    # tables stacked and indexed inside the Pallas contractions
    eng = ServingEngine(graph, params, plan=ServePlan.preset("tpu"))
    assert {"din_attn::T", "din_attn::u_part",
            "user_seq_emb"} <= eng.lazy_gather_inputs
    got = eng.score_coalesced(reqs)
    assert eng.coalesced_calls == 1 and eng.rep_stack_bytes > 0
    vani = ServingEngine(graph, params, plan=ServePlan.preset("vanilla"))
    ref = ReferenceScorer(graph, params)
    atol, rtol = SCORE_TOL["cpu"]
    for g, v, r in zip(got, vani.score_coalesced(reqs), reqs):
        want = ref(r)
        np.testing.assert_allclose(g.scores, want, atol=atol, rtol=rtol)
        np.testing.assert_allclose(v.scores, want, atol=atol, rtol=rtol)
    eng.close()
    vani.close()
