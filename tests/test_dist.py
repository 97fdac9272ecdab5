"""repro.dist subsystem tests: serving pspecs, the sharding policy, int8
compression, the collective-aware bucket planner, the kernel-side user-rep
gather, and multi-PROCESS stage-2 sharding (2 ``jax.distributed`` workers,
subprocess) — sharded fp32 scores must match the float32 reference within
the stated tolerance across vani/uoi/mari."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import policy
from repro.dist.compress import dequantize_int8, quantize_int8
from repro.dist.sharding import candidate_pspecs, dp_axes, named
from repro.dist.topology import (Topology, bucket_for, candidate_mesh,
                                 plan_buckets)

_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


# ---------------------------------------------------------------------------
# specs + policy + topology (pure / single-device)
# ---------------------------------------------------------------------------

class TestServingSpecs:
    def test_candidate_mesh_and_pspecs(self):
        mesh = candidate_mesh()
        assert mesh.axis_names == ("cand",)
        assert _pow2(int(mesh.devices.size))
        (p_params, p_table, p_uidx, p_cand), out = candidate_pspecs(mesh)
        assert p_params.spec == jax.sharding.PartitionSpec()
        assert p_table.spec == jax.sharding.PartitionSpec()
        assert p_uidx.spec == jax.sharding.PartitionSpec("cand")
        assert p_cand.spec == jax.sharding.PartitionSpec("cand")
        # single-process mesh: scores stay device-sharded...
        assert out.spec == jax.sharding.PartitionSpec("cand")
        # ...unless the cross-host form is forced
        _, out_repl = candidate_pspecs(mesh, replicate_out=True)
        assert out_repl.spec == jax.sharding.PartitionSpec()

    def test_candidate_mesh_rejects_non_pow2(self):
        with pytest.raises(ValueError, match="power of two"):
            candidate_mesh(3)

    def test_dp_axes_and_named(self):
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh((1, 1), ("data", "model"))
        assert dp_axes(mesh) == ("data",)
        pod = make_host_mesh((1, 1, 1), ("pod", "data", "model"))
        assert dp_axes(pod) == ("pod", "data")
        tree = {"a": jax.sharding.PartitionSpec(None, "model")}
        sh = named(mesh, tree)
        assert isinstance(sh["a"], jax.sharding.NamedSharding)

    def test_family_state_pspecs_cover_trees(self):
        """Every family's state-spec tree must mirror its state tree."""
        from repro import configs as cfgreg
        from repro.dist.sharding import (gnn_state_pspecs, lm_state_pspecs,
                                         recsys_state_pspecs)
        from repro.graph.executor import init_graph_params
        from repro.train.optim import adam

        cfg = cfgreg.get_config("qwen3-14b").CONFIG
        sp = lm_state_pspecs(cfg)
        assert set(sp) == {"params", "opt"}
        assert set(sp["opt"]) == {"mu", "nu", "master", "step"}

        graph, _ = cfgreg.get_config("deepfm").smoke_build()()
        params = jax.eval_shape(
            lambda: init_graph_params(graph, jax.random.PRNGKey(0)))
        opt_sds = jax.eval_shape(adam(1e-3).init, params)
        rp = recsys_state_pspecs(graph)
        jax.tree_util.tree_map(lambda a, b: None, params, rp["params"],
                               is_leaf=lambda x: not isinstance(x, dict))
        jax.tree_util.tree_map(lambda a, b: None, opt_sds, rp["opt"],
                               is_leaf=lambda x: not isinstance(x, dict))

        gp = gnn_state_pspecs({"w": jax.ShapeDtypeStruct((4, 4), jnp.float32)})
        assert list(gp["params"]["w"]) == [None, None]

    def test_boundary_pspecs_replicated(self):
        from repro.core.mari import mari_rewrite
        from repro.core.split import split_two_stage
        from repro.models.ranking import (PaperRankingConfig,
                                          build_paper_ranking_model)
        graph, _ = build_paper_ranking_model(PaperRankingConfig().scaled(0.03))
        split = split_two_stage(mari_rewrite(graph).graph)
        bp = split.boundary_pspecs()
        assert set(bp) == set(split.boundary_specs)
        for name, spec in bp.items():
            assert len(spec) == 1 + len(split.boundary_specs[name])
            assert all(p is None for p in spec)


class TestPolicy:
    def test_nesting_and_constrain(self):
        assert policy.get("k") is None
        with policy.use(k=1, other="x"):
            assert policy.get("k") == 1
            with policy.use(k=2):
                assert policy.get("k") == 2
                assert policy.get("other") == "x"
            assert policy.get("k") == 1
        assert policy.get("k") is None
        # constrain without a registered sharding is identity
        x = jnp.ones((3,))
        np.testing.assert_array_equal(policy.constrain(x, "residual"), x)

    def test_thread_isolation(self):
        import threading
        seen = {}

        def peek():
            seen["worker"] = policy.get("k")

        with policy.use(k=42):
            t = threading.Thread(target=peek)
            t.start()
            t.join()
        assert seen["worker"] is None


class TestBucketPlanner:
    def test_property_sweep(self):
        """Every shard receives equal, power-of-two-aligned work and
        padding never exceeds one bucket — for all (pool, shards)."""
        for shards in (1, 2, 4, 8, 16):
            for pool in (1, 2, 3, 7, 15, 16, 17, 100, 511, 512, 1000,
                         4096, 4097, 10000):
                plan = plan_buckets(pool, shards, min_bucket=32,
                                    max_batch=1024)
                assert plan, (pool, shards)
                for b in plan:
                    assert _pow2(b), (pool, shards, plan)
                    assert b % shards == 0, (pool, shards, plan)
                    assert _pow2(b // shards), (pool, shards, plan)
                total = sum(plan)
                assert total >= pool
                # padding fits inside the (one) tail bucket
                assert total - pool < plan[-1], (pool, shards, plan)
                # every bucket except the tail is full-sized
                assert all(b == 1024 for b in plan[:-1]), (pool, shards, plan)

    def test_bucket_for_invariants(self):
        assert bucket_for(1, 8, min_bucket=2, max_batch=64) == 8
        assert bucket_for(100, 4, min_bucket=16, max_batch=4096) == 128
        assert bucket_for(5000, 4, min_bucket=16, max_batch=1024) == 1024
        with pytest.raises(ValueError, match="power of two"):
            bucket_for(10, 3)

    def test_non_pow2_max_batch_cap_rounds_down_when_sharded(self):
        """A cap-sized bucket must divide over the mesh: shards > 1 round a
        non-pow2 max_batch down to a power of two; shards == 1 keep the
        seed's raw-cap behavior."""
        assert bucket_for(100, 8, min_bucket=16, max_batch=100) == 64
        assert bucket_for(100, 1, min_bucket=16, max_batch=100) == 100
        for b in plan_buckets(1000, 8, min_bucket=16, max_batch=100):
            assert _pow2(b) and b % 8 == 0
        # cap below the shard count still yields a shard-divisible bucket
        assert bucket_for(3, 8, min_bucket=2, max_batch=5) == 8

    def test_empty_pool(self):
        assert plan_buckets(0, 4) == []


class TestCompression:
    def test_int8_roundtrip_bound_fixed_vectors(self):
        for arr in ([0.0], [0.0, 0.0], [-1e3, 333.3, 0.1], [1e-6],
                    list(np.linspace(-1, 1, 64)), [127.0, -127.0]):
            x = jnp.asarray(arr, jnp.float32)
            q, scale = quantize_int8(x)
            assert q.dtype == jnp.int8
            err = np.abs(np.asarray(dequantize_int8(q, scale) - x))
            assert err.max() <= float(scale) / 2 + 1e-6

    def test_compressed_psum_error_feedback_closes(self):
        from jax.experimental.shard_map import shard_map
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.dist.compress import compressed_psum
        mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
        g = {"w": jnp.asarray([-2.0, 0.5, 1.7], jnp.float32)}
        out, err = shard_map(lambda t: compressed_psum(t, "data"),
                             mesh=mesh, in_specs=(P(),),
                             out_specs=(P(), P()))(g)
        np.testing.assert_allclose(np.asarray(out["w"] + err["w"]),
                                   g["w"], atol=1e-6)


# ---------------------------------------------------------------------------
# kernel-side gather: table indexed by user_index at accumulator-init load
# ---------------------------------------------------------------------------

class TestKernelGather:
    def _parts(self, key, B, Dr, d):
        ks = jax.random.split(key, 4)
        return ([(jax.random.normal(ks[0], (B, Dr)),
                  jax.random.normal(ks[1], (Dr, d)))],
                jax.random.normal(ks[2], (d,)))

    @pytest.mark.parametrize("B,U,Dr,d", [(32, 4, 24, 20), (7, 1, 5, 3),
                                          (64, 8, 130, 129)])
    def test_ops_bit_identical_to_explicit_gather(self, B, U, Dr, d):
        from repro.kernels.mari_matmul import mari_matmul_fused_groups
        key = jax.random.PRNGKey(B + U + d)
        parts, b = self._parts(key, B, Dr, d)
        table = jax.random.normal(jax.random.fold_in(key, 1), (U, d))
        idx = jax.random.randint(jax.random.fold_in(key, 2), (B,), 0, U)
        ref = mari_matmul_fused_groups(
            parts, b, acc0=jnp.take(table, idx, axis=0),
            activation="relu", interpret=True)
        out = mari_matmul_fused_groups(
            parts, b, acc0=table, user_index=idx,
            activation="relu", interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_no_batched_stream_epilogue_gather(self):
        """All-user parts: the gathered epilogue row block is still exact."""
        from repro.kernels.mari_matmul import mari_matmul_fused_groups
        key = jax.random.PRNGKey(0)
        parts = [(jax.random.normal(key, (1, 6)),
                  jax.random.normal(jax.random.fold_in(key, 1), (6, 5)))]
        table = jax.random.normal(jax.random.fold_in(key, 2), (4, 5))
        idx = jnp.asarray([3, 0, 0, 2, 1], jnp.int32)
        ref = mari_matmul_fused_groups(
            parts, None, acc0=jnp.take(table, idx, axis=0),
            activation="sigmoid", interpret=True)
        out = mari_matmul_fused_groups(
            parts, None, acc0=table, user_index=idx,
            activation="sigmoid", interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def test_engine_end_to_end_bit_identical(self):
        """ServingEngine(kernel_gather=True) == materialized-gather engine,
        coalesced multi-user, on the paper's ranking model."""
        from repro.data.features import make_recsys_feeds
        from repro.graph.executor import init_graph_params
        from repro.models.ranking import (PaperRankingConfig,
                                          build_paper_ranking_model)
        from repro.serve.engine import ServeRequest, ServingEngine
        graph, _ = build_paper_ranking_model(PaperRankingConfig().scaled(0.03))
        params = init_graph_params(graph, jax.random.PRNGKey(0))
        user_in = {n.name for n in graph.input_nodes()
                   if n.attrs.get("domain") == "user"}

        def req(uid, n, seed):
            feeds = make_recsys_feeds(graph, n, jax.random.PRNGKey(seed))
            return ServeRequest(
                uid, {k: v for k, v in feeds.items() if k in user_in},
                {k: v for k, v in feeds.items() if k not in user_in})

        reqs = [req(0, 21, 1), req(1, 40, 2)]
        ref = ServingEngine(graph, params, mode="mari", max_batch=64,
                            min_bucket=16, use_pallas=True)
        lazy = ServingEngine(graph, params, mode="mari", max_batch=64,
                             min_bucket=16, use_pallas=True,
                             kernel_gather=True)
        # the paper model must actually exercise the lazy path — an empty
        # eligibility set would degrade this into ref-vs-ref
        assert lazy.kernel_gather and len(lazy.lazy_gather_inputs) > 0
        assert not ref.lazy_gather_inputs
        for a, b in zip(ref.score_coalesced(reqs),
                        lazy.score_coalesced(reqs)):
            np.testing.assert_array_equal(a.scores, b.scores)


# ---------------------------------------------------------------------------
# multi-process stage-2 sharding (the acceptance-criteria test)
# ---------------------------------------------------------------------------

class TestMultiProcessServing:
    def test_spawn_refuses_several_processes_off_the_cpu(self):
        """Without JAX_PLATFORMS=cpu the runner picks no platform and
        forces no host devices: several processes on one host cannot share
        its chips, so --spawn 2 is refused before any worker starts."""
        env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
        env["PYTHONPATH"] = (os.path.abspath(_SRC) + os.pathsep
                             + env.get("PYTHONPATH", ""))
        p = subprocess.run(
            [sys.executable, "-m", "repro.dist.runner", "--spawn", "2"],
            env=env, capture_output=True, text=True, timeout=120)
        assert p.returncode == 2, p.stderr[-2000:]
        assert "use --spawn 1" in p.stderr

    def test_two_worker_bit_identity(self):
        """2 jax.distributed workers × 2 forced host devices: SPMD sharded
        stage-2 scores match the float32 reference within the stated CPU
        tolerance across vani/uoi/mari, with collective-aware bucketing
        on (differently shaped executables are not bit-identical)."""
        env = dict(os.environ)
        env["PYTHONPATH"] = (os.path.abspath(_SRC) + os.pathsep
                             + env.get("PYTHONPATH", ""))
        env.setdefault("JAX_PLATFORMS", "cpu")
        # the workers are an entry point and turn the persistent compile
        # cache on; a test run leaves no cache behind
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
        # --max-batch 100 is deliberately non-pow2: the sharded engines
        # normalize it to a shard-divisible pow2 cap — different packing,
        # same rows, so the reference check also covers packing
        p = subprocess.run(
            [sys.executable, "-m", "repro.dist.runner", "--spawn", "2",
             "--devices-per-process", "2", "--verify",
             "--max-batch", "100", "--modes", "vani,uoi,mari"],
            env=env, capture_output=True, text=True, timeout=570)
        assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-3000:])
        # gloo's connection notices share the workers' stdout
        recs = [json.loads(line) for line in p.stdout.strip().splitlines()
                if line.startswith("{")]
        done = [r for r in recs if r.get("within_tol")]
        assert {r["mode"] for r in done} == {"vani", "uoi", "mari"}
        assert all(r["processes"] == 2 and r["shards"] == 4 for r in done)
        assert recs[-1] == {"ok": True, "records": 3}


class TestEngineShardingConfig:
    def test_compress_scores_requires_shard_candidates(self):
        from repro.models.recsys import build_din
        from repro.graph.executor import init_graph_params
        from repro.serve.engine import ServingEngine
        graph, _ = build_din(embed_dim=4, seq_len=6, attn_mlp=(8, 4),
                             mlp=(8,), item_vocab=32, user_profile_dim=6,
                             context_dim=3)
        params = init_graph_params(graph, jax.random.PRNGKey(0))
        with pytest.raises(ValueError, match="shard_candidates"):
            ServingEngine(graph, params, compress_scores=True)

    def test_compress_scores_within_int8_bound(self):
        """End-to-end compress_scores path (quantize -> all-gather ->
        per-shard dequantize): scores stay within the int8 error bound of
        the exact engine. Single-device mesh — the quantized gather code
        path is identical at any shard count (multi-shard/multi-process
        forms run in the dist bench and runner CLI)."""
        from repro.data.features import make_recsys_feeds
        from repro.graph.executor import init_graph_params
        from repro.models.ranking import (PaperRankingConfig,
                                          build_paper_ranking_model)
        from repro.serve.engine import ServeRequest, ServingEngine
        graph, _ = build_paper_ranking_model(PaperRankingConfig().scaled(0.03))
        params = init_graph_params(graph, jax.random.PRNGKey(0))
        user_in = {n.name for n in graph.input_nodes()
                   if n.attrs.get("domain") == "user"}
        feeds = make_recsys_feeds(graph, 30, jax.random.PRNGKey(1))
        req = ServeRequest(
            0, {k: v for k, v in feeds.items() if k in user_in},
            {k: v for k, v in feeds.items() if k not in user_in})
        ref = ServingEngine(graph, params, mode="mari", max_batch=64,
                            min_bucket=16, shard_candidates=True)
        cmp_eng = ServingEngine(graph, params, mode="mari", max_batch=64,
                                min_bucket=16, shard_candidates=True,
                                compress_scores=True)
        assert cmp_eng._cgather is not None
        a = ref.score(req).scores
        b = cmp_eng.score(req).scores
        tol = float(np.abs(a).max()) / 127.0 / 2.0 + 1e-6
        np.testing.assert_allclose(b, a, atol=tol)
        # quantization is real: bit-identity should NOT generally hold
        assert b.dtype == a.dtype and b.shape == a.shape

    def test_batcher_rejects_multiprocess_engine(self):
        """Timing-dependent group formation would desynchronize the SPMD
        collective schedule — the batcher must refuse such engines."""
        import types
        from repro.serve.batcher import CoalescingBatcher
        fake = types.SimpleNamespace(_multiproc=True, max_batch=128)
        with pytest.raises(ValueError, match="multi-process"):
            CoalescingBatcher(fake)

    def test_non_pow2_max_batch_normalized_when_sharded(self):
        """On a 1-device mesh the cap keeps seed behavior; the planner
        invariant is exercised directly (multi-device normalization is
        covered by bucket_for + the forced-device subprocess paths)."""
        from repro.models.recsys import build_din
        from repro.graph.executor import init_graph_params
        from repro.serve.engine import ServingEngine
        graph, _ = build_din(embed_dim=4, seq_len=6, attn_mlp=(8, 4),
                             mlp=(8,), item_vocab=32, user_profile_dim=6,
                             context_dim=3)
        params = init_graph_params(graph, jax.random.PRNGKey(0))
        # shard count pinned to 1 so the assertion holds on any machine
        eng = ServingEngine(graph, params, max_batch=100, min_bucket=8,
                            shard_candidates=1)
        assert eng._n_shards == 1 and eng.max_batch == 100
        assert eng._bucket(100) == 100          # raw cap, seed behavior


class TestTopology:
    def test_single_process_topology_is_degenerate(self):
        topo = Topology()
        assert not topo.is_distributed
        topo.initialize()        # no coordinator handshake, no-op
        assert len(jax.devices()) >= 1

    def test_from_env_roundtrip(self, monkeypatch):
        monkeypatch.setenv("REPRO_NUM_PROCESSES", "4")
        monkeypatch.setenv("REPRO_PROCESS_ID", "2")
        monkeypatch.setenv("REPRO_COORDINATOR", "localhost:7777")
        topo = Topology.from_env()
        assert (topo.num_processes, topo.process_id) == (4, 2)
        assert topo.coordinator == "localhost:7777"
        assert topo.is_distributed
