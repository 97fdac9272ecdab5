"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode).

Shapes sweep odd/aligned sizes and dtypes per the kernel contract.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (din_attention, dot_interaction, embedding_bag,
                           gather_einsum, gather_einsum_ref,
                           mari_matmul_fused, mari_matmul_fused_groups)
from repro.kernels.gather_einsum.kernel import parse_spec
from repro.kernels.din_attention.ref import din_attention_ref
from repro.kernels.dot_interaction.ref import dot_interaction_ref
from repro.kernels.embedding_bag.ref import embedding_bag_ref
from repro.kernels.mari_matmul.ref import (mari_matmul_groups_ref,
                                           mari_matmul_ref)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


class TestMariMatmul:
    @pytest.mark.parametrize("B,Du,Dr,d", [
        (1, 8, 8, 8), (16, 100, 50, 64), (100, 4000 // 8, 1000 // 8, 512 // 8),
        (257, 33, 129, 65), (512, 128, 256, 128),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep(self, B, Du, Dr, d, dtype):
        ks = jax.random.split(jax.random.PRNGKey(B + Du), 5)
        xu = jax.random.normal(ks[0], (1, Du), dtype)
        xr = jax.random.normal(ks[1], (B, Dr), dtype)
        wu = jax.random.normal(ks[2], (Du, d), dtype)
        wr = jax.random.normal(ks[3], (Dr, d), dtype)
        b = jax.random.normal(ks[4], (d,), dtype)
        out = mari_matmul_fused(xu, xr, wu, wr, b, interpret=True)
        ref = mari_matmul_ref(xu, xr, wu, wr, b)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(ref, np.float32),
                                   **_tol(dtype))

    def test_no_bias(self):
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        out = mari_matmul_fused(jax.random.normal(ks[0], (1, 16)),
                                jax.random.normal(ks[1], (32, 24)),
                                jax.random.normal(ks[2], (16, 8)),
                                jax.random.normal(ks[3], (24, 8)),
                                interpret=True)
        assert out.shape == (32, 8) and np.isfinite(out).all()

    @pytest.mark.parametrize("activation", ["relu", "sigmoid", "gelu", "tanh"])
    @pytest.mark.parametrize("B,Du,Dr,d", [(64, 48, 96, 32), (257, 33, 129, 65)])
    def test_activation_epilogue(self, activation, B, Du, Dr, d):
        """Bias + activation fused into the kernel epilogue (non-aligned
        shapes included) match the jnp oracle."""
        ks = jax.random.split(jax.random.PRNGKey(d), 5)
        xu = jax.random.normal(ks[0], (1, Du))
        xr = jax.random.normal(ks[1], (B, Dr))
        wu = jax.random.normal(ks[2], (Du, d))
        wr = jax.random.normal(ks[3], (Dr, d))
        b = jax.random.normal(ks[4], (d,))
        out = mari_matmul_fused(xu, xr, wu, wr, b, activation=activation,
                                interpret=True)
        ref = mari_matmul_groups_ref([(xu, wu), (xr, wr)], b,
                                     activation=activation)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


class TestMariMatmulGroups:
    """Multi-group / fragmented variant: Σ_g x_g W_g with batch-1 (user)
    operands folded into the accumulator-init row."""

    def _parts(self, key, layout, B, d):
        parts = []
        for j, (dom, w_) in enumerate(layout):
            x = jax.random.normal(jax.random.fold_in(key, j),
                                  (1 if dom == "u" else B, w_))
            w = jax.random.normal(jax.random.fold_in(key, 100 + j), (w_, d))
            parts.append((x, w))
        return parts

    @pytest.mark.parametrize("activation", ["identity", "relu", "sigmoid"])
    def test_fragmented_interleaved(self, activation):
        B, d = 53, 17   # deliberately non-aligned
        layout = [("u", 5), ("i", 9), ("u", 13), ("i", 3), ("u", 4)]
        parts = self._parts(jax.random.PRNGKey(1), layout, B, d)
        b = jax.random.normal(jax.random.PRNGKey(2), (d,))
        out = mari_matmul_fused_groups(parts, b, activation=activation,
                                       interpret=True)
        ref = mari_matmul_groups_ref(parts, b, activation=activation)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

    def test_matches_vanilla_tiled(self):
        """Groups form == vanilla (B, D) @ (D, d) over tiled features."""
        from repro.core.mari import matmul_vanilla
        B, d = 31, 8
        layout = [("u", 6), ("i", 4), ("u", 5)]
        parts = self._parts(jax.random.PRNGKey(3), layout, B, d)
        tiled = jnp.concatenate(
            [jnp.broadcast_to(x, (B,) + x.shape[1:]) for x, _ in parts], -1)
        w = jnp.concatenate([w for _, w in parts], 0)
        out = mari_matmul_fused_groups(parts, interpret=True)
        np.testing.assert_allclose(out, matmul_vanilla(tiled, w),
                                   rtol=2e-4, atol=2e-4)

    def test_acc0_row(self):
        """Precomputed (1, d) partial (two-stage serving) seeds the
        accumulator."""
        B, d = 16, 8
        parts = self._parts(jax.random.PRNGKey(4), [("i", 7)], B, d)
        acc0 = jax.random.normal(jax.random.PRNGKey(5), (1, d))
        out = mari_matmul_fused_groups(parts, acc0=acc0, activation="relu",
                                       interpret=True)
        ref = mari_matmul_groups_ref(parts, acc0=acc0, activation="relu")
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

    def test_batch_one_all_user(self):
        parts = self._parts(jax.random.PRNGKey(6), [("u", 5), ("u", 3)], 1, 4)
        out = mari_matmul_fused_groups(parts, interpret=True)
        ref = mari_matmul_groups_ref(parts)
        assert out.shape == (1, 4)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


class TestExecutorPallasPath:
    """kernel == _run_mari_dense (jnp) == vanilla dense graph, with bias,
    activation, and non-aligned shapes."""

    def _graph(self, activation="relu", use_bias=True):
        from repro.graph.ir import GraphBuilder
        b = GraphBuilder()
        u = b.input("u", (19,), "user")
        i = b.input("i", (11,), "item")
        x = b.input("x", (6,), "cross")
        c = b.concat("c", [u, i, x])
        f1 = b.dense("f1", c, 21, activation=activation, use_bias=use_bias)
        f2 = b.dense("f2", f1, 1)
        b.output(f2)
        return b.graph

    @pytest.mark.parametrize("activation", ["relu", "sigmoid"])
    @pytest.mark.parametrize("use_bias", [True, False])
    @pytest.mark.parametrize("fragment", [False, True])
    def test_three_way_equivalence(self, activation, use_bias, fragment):
        from repro.core import apply_mari
        from repro.graph.executor import Executor, init_graph_params
        g = self._graph(activation, use_bias)
        params = init_graph_params(g, jax.random.PRNGKey(0))
        feeds = {
            "u": jax.random.normal(jax.random.PRNGKey(1), (1, 19)),
            "i": jax.random.normal(jax.random.PRNGKey(2), (13, 11)),
            "x": jax.random.normal(jax.random.PRNGKey(3), (13, 6)),
        }
        ref = Executor(g, "vani").run(params, feeds)["f2"]   # vanilla dense
        mg, mp, _ = apply_mari(g, params, fragment=fragment)
        out_jnp = Executor(mg, "uoi").run(mp, feeds)["f2"]
        out_pal = Executor(mg, "uoi", use_pallas=True).run(mp, feeds)["f2"]
        np.testing.assert_allclose(out_jnp, ref, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(out_pal, ref, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(out_pal, out_jnp, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                                   ("tpu", False),
                                                   ("gpu", None)])
    def test_executor_pallas_mode_follows_backend(self, monkeypatch, backend,
                                                  interpret):
        """Interpreted only on the CPU, compiled on the TPU, refused
        elsewhere: the interpreter never stands in for a device."""
        from repro.graph.executor import Executor
        g = self._graph("relu", True)
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        if interpret is None:
            with pytest.raises(ValueError, match="backend 'gpu'"):
                Executor(g, "uoi", use_pallas=True)
        else:
            assert Executor(g, "uoi",
                            use_pallas=True).pallas_interpret is interpret


@pytest.mark.parametrize("op", [mari_matmul_fused, mari_matmul_fused_groups,
                                gather_einsum, din_attention,
                                dot_interaction, embedding_bag],
                         ids=lambda op: op.__name__)
def test_kernel_ops_default_to_compiled(op):
    """A caller on the chip that forgets the flag gets the kernel, not the
    interpreter."""
    import inspect
    assert inspect.signature(op).parameters["interpret"].default is False


class TestGatherEinsum:
    """Gather-aware einsum family (attention-side analogue of the
    mari_matmul kernel gather): the stacked (U, ...) table is indexed by
    ``user_index`` inside the contraction; the gathered (B, ...) operand
    never materializes. Must match jnp.take(mode="clip") + einsum."""

    SPECS = ("bd,uldh->blh", "bl,uld->bd", "blh,uh->bl")

    def _args(self, spec, sizes, seed=0, idx_high=None):
        x_sub, t_sub, _, row_spec = parse_spec(spec)
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        x = jax.random.normal(ks[0], tuple(sizes[c] for c in x_sub))
        t = jax.random.normal(ks[1], tuple(sizes[c] for c in t_sub))
        idx = jax.random.randint(ks[2], (sizes["b"],), 0,
                                 idx_high or sizes["u"])
        return x, t, idx, row_spec

    @pytest.mark.parametrize("U", [1, 2, 3, 5, 8])   # non-pow2 included
    @pytest.mark.parametrize("spec", SPECS)
    def test_matches_take_einsum(self, spec, U):
        sizes = dict(u=U, b=13, l=7, d=6, h=5)
        x, t, idx, row_spec = self._args(spec, sizes, seed=U)
        out = gather_einsum(spec, x, t, idx, interpret=True)
        expected = jnp.einsum(row_spec, x,
                              jnp.take(t, idx, axis=0, mode="clip"))
        np.testing.assert_allclose(out, expected, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(out, gather_einsum_ref(spec, x, t, idx),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("B,L,D,h", [
        (1, 3, 4, 2), (53, 12, 9, 17), (300, 33, 18, 16),
    ])
    @pytest.mark.parametrize("spec", SPECS)
    def test_shape_sweep(self, spec, B, L, D, h):
        """Odd / tile-crossing shapes (B above and below the 256-row block,
        non-aligned feature dims)."""
        sizes = dict(u=3, b=B, l=L, d=D, h=h)
        x, t, idx, _ = self._args(spec, sizes, seed=B + L)
        out = gather_einsum(spec, x, t, idx, interpret=True)
        ref = gather_einsum_ref(spec, x, t, idx)
        assert out.shape == ref.shape
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("spec", SPECS)
    def test_u1_rows_bit_identical_to_coalesced(self, spec):
        """Row b depends only on (x[b], table[idx[b]]): slicing one user's
        table down to U=1 reproduces that user's rows BIT-identically —
        the invariant that makes a single request the degenerate case of
        the coalesced batch."""
        sizes = dict(u=4, b=24, l=5, d=6, h=3)
        x, t, idx, _ = self._args(spec, sizes, seed=11)
        out = gather_einsum(spec, x, t, idx, interpret=True)
        for u in range(sizes["u"]):
            rows = np.asarray(idx) == u
            if not rows.any():
                continue
            out_u1 = gather_einsum(spec, x, t[u:u + 1],
                                   jnp.zeros_like(idx), interpret=True)
            np.testing.assert_array_equal(np.asarray(out)[rows],
                                          np.asarray(out_u1)[rows])

    @pytest.mark.parametrize("spec", SPECS)
    def test_out_of_range_index_clamps(self, spec):
        """Padded-row hazard: an out-of-range index must read the last
        real slot (clip), never wrap (numpy) or NaN-fill (jax default)."""
        sizes = dict(u=3, b=9, l=4, d=5, h=2)
        x, t, idx, _ = self._args(spec, sizes, seed=7, idx_high=9)
        assert (np.asarray(idx) >= sizes["u"]).any()   # seed chosen to OOB
        out = gather_einsum(spec, x, t, idx, interpret=True)
        assert np.isfinite(np.asarray(out)).all()
        clamped = jnp.clip(idx, 0, sizes["u"] - 1)
        np.testing.assert_array_equal(
            np.asarray(out),
            np.asarray(gather_einsum(spec, x, t, clamped, interpret=True)))

    @pytest.mark.parametrize("bad", [
        "ud,bld->bl",        # operands swapped
        "bd,uldh->ulh",      # output keyed by user, not row
        "bdd,ud->bd",        # repeated dim
        "bd,uldh,bl->blh",   # three operands
        "bd,uldh->blz",      # output dim from nowhere
    ])
    def test_bad_specs_raise(self, bad):
        with pytest.raises(ValueError):
            parse_spec(bad)


class TestEmbeddingBag:
    @pytest.mark.parametrize("V,D,S,nnz", [
        (16, 8, 4, 20), (100, 32, 17, 123), (1000, 128, 64, 512),
    ])
    @pytest.mark.parametrize("combiner", ["sum", "mean"])
    def test_sweep(self, V, D, S, nnz, combiner):
        ks = jax.random.split(jax.random.PRNGKey(V + nnz), 3)
        table = jax.random.normal(ks[0], (V, D))
        ids = jax.random.randint(ks[1], (nnz,), 0, V)
        segs = jax.random.randint(ks[2], (nnz,), 0, S)
        out = embedding_bag(table, ids, segs, num_segments=S,
                            combiner=combiner, interpret=True)
        ref = embedding_bag_ref(table, ids, segs, S, combiner)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)

    def test_empty_segments_zero(self):
        table = jnp.ones((8, 4))
        ids = jnp.array([0, 1], jnp.int32)
        segs = jnp.array([2, 2], jnp.int32)   # segments 0,1,3 empty
        out = embedding_bag(table, ids, segs, num_segments=4, interpret=True)
        np.testing.assert_array_equal(out[0], 0)
        np.testing.assert_array_equal(out[1], 0)
        np.testing.assert_array_equal(out[3], 0)
        np.testing.assert_array_equal(out[2], 2 * jnp.ones(4))

    def test_unsorted_input(self):
        ks = jax.random.split(jax.random.PRNGKey(9), 3)
        table = jax.random.normal(ks[0], (50, 16))
        ids = jax.random.randint(ks[1], (64,), 0, 50)
        segs = jax.random.permutation(
            ks[2], jnp.repeat(jnp.arange(8), 8))
        out = embedding_bag(table, ids, segs, num_segments=8, interpret=True)
        ref = embedding_bag_ref(table, ids, segs, 8)
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


class TestDotInteraction:
    @pytest.mark.parametrize("B,F,D", [(8, 4, 8), (37, 27, 16), (128, 27, 128)])
    @pytest.mark.parametrize("keep_self", [False, True])
    def test_sweep(self, B, F, D, keep_self):
        x = jax.random.normal(jax.random.PRNGKey(B + F), (B, F, D))
        out = dot_interaction(x, keep_self=keep_self, interpret=True)
        ref = dot_interaction_ref(x, keep_self=keep_self)
        assert out.shape[1] == (F * (F + 1) // 2 if keep_self
                                else F * (F - 1) // 2)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


class TestDinAttention:
    @pytest.mark.parametrize("B,L,D", [(4, 5, 8), (33, 20, 18), (128, 100, 18)])
    def test_sweep(self, B, L, D):
        h1, h2 = 16, 8
        ks = jax.random.split(jax.random.PRNGKey(B + L), 6)
        q = jax.random.normal(ks[0], (B, D))
        keys = jax.random.normal(ks[1], (L, D))
        mask = jax.random.bernoulli(ks[2], 0.9, (L,)).at[0].set(True)
        w1 = jax.random.normal(ks[3], (4 * D, h1)) * 0.2
        w2 = jax.random.normal(ks[4], (h1, h2)) * 0.2
        w3 = jax.random.normal(ks[5], (h2, 1)) * 0.2
        b1, b2, b3 = jnp.zeros(h1), jnp.zeros(h2), jnp.zeros(1)
        out = din_attention(q, keys, mask, w1, b1, w2, b2, w3, b3,
                            interpret=True)
        ref = din_attention_ref(q, keys, mask, w1, b1, w2, b2, w3, b3)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)

    def test_matches_nn_target_attention(self):
        """Kernel agrees with the graph executor's target_attention op."""
        from repro.nn.attention import target_attention
        from repro.nn.layers import dense_apply
        B, L, D, h1, h2 = 9, 7, 6, 12, 5
        ks = jax.random.split(jax.random.PRNGKey(3), 6)
        q = jax.random.normal(ks[0], (B, D))
        keys = jax.random.normal(ks[1], (1, L, D))
        mask = jnp.ones((1, L), bool)
        p = {"layer_0": {"w": jax.random.normal(ks[2], (4 * D, h1)) * 0.3,
                         "b": jnp.zeros(h1)},
             "layer_1": {"w": jax.random.normal(ks[3], (h1, h2)) * 0.3,
                         "b": jnp.zeros(h2)},
             "layer_2": {"w": jax.random.normal(ks[4], (h2, 1)) * 0.3,
                         "b": jnp.zeros(1)}}

        def mlp(x):
            x = jax.nn.relu(dense_apply(p["layer_0"], x))
            x = jax.nn.relu(dense_apply(p["layer_1"], x))
            return dense_apply(p["layer_2"], x)

        ref = target_attention(q, keys, mask, mlp)
        out = din_attention(q, keys[0], mask[0],
                            p["layer_0"]["w"], p["layer_0"]["b"],
                            p["layer_1"]["w"], p["layer_1"]["b"],
                            p["layer_2"]["w"], p["layer_2"]["b"],
                            interpret=True)
        np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)
