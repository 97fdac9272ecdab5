"""The benchmark's own float32 references agree with the program's
reference scorer (the un-rewritten graph run by the program's executor) at
smoke size on the CPU, within the CPU tolerance, on the benchmark's
weights and features; a change to either shows here."""
import jax
import numpy as np
import pytest

from chipbench import model, spec
from chipbench.tests.conftest import CONFIGS, smoke_config
from chipbench.traffic import Traffic


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_agrees_with_the_program_reference_scorer(name):
    from repro.serve import ServeRequest
    from repro.serve.reference import SCORE_TOL, ReferenceScorer

    b = spec.load_benchmark()
    cfg = smoke_config(name)
    ref = spec.load_reference(spec.ROOT, b, name)
    shapes = ref.param_shapes(cfg)
    graph = model.program_graph(cfg)
    model.check_param_shapes(graph, shapes)
    weights = model.make_weights(shapes, cfg["init"], 2**33 + 5)
    mix = {"loop": "closed", "requests": 4, "clients": 1,
           "users": {"kind": "zipf", "s": 1.1, "universe": 100},
           "pool": {"min": 30, "max": 300}, "user_feature_pool": 8,
           "candidate_rows": 2048, "base_seed": 1}
    traffic = Traffic(mix, ref.input_specs(cfg), cfg, 11, 1.0)
    ours = model.Reference(ref, cfg, weights)
    theirs = ReferenceScorer(graph, weights)
    atol, rtol = SCORE_TOL["cpu"]
    for i in range(traffic.n):
        uid = int(traffic.uids[i])
        user, cand = traffic.user_feeds(uid), traffic.cand_feeds(i)
        got = ours(user, cand)
        want = theirs(ServeRequest(
            user_id=uid, user_feeds={k: jax.numpy.asarray(v)
                                     for k, v in user.items()},
            candidate_feeds={k: jax.numpy.asarray(v)
                             for k, v in cand.items()}))
        assert got.shape == want.shape == (len(cand[next(iter(cand))]),
                                           cfg.get("n_tasks", 1))
        np.testing.assert_allclose(got, want, atol=atol, rtol=rtol)


def test_weights_are_made_from_the_seed():
    bench = spec.load_benchmark()
    for name in CONFIGS:
        cfg = smoke_config(name)
        shapes = spec.load_reference(spec.ROOT, bench,
                                     name).param_shapes(cfg)
        a = model.make_weights(shapes, cfg["init"], 123)
        b = model.make_weights(shapes, cfg["init"], 123)
        c = model.make_weights(shapes, cfg["init"], 124)
        for x, y, z in zip(jax.tree.leaves(a), jax.tree.leaves(b),
                           jax.tree.leaves(c)):
            np.testing.assert_array_equal(x, y, err_msg=name)
            assert not np.array_equal(x, z), name
