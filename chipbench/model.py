"""Weights made from the seed, the program under test built around them,
and the plain reference run over the same weights and features.

The weights are the benchmark's own: one jitted call on the device makes
every leaf the reference module names (``param_shapes``), in float32, the
type the model is served in. The program's model, built from the
``repro.configs`` registry, must name the same leaves with the same
shapes, or the run stops before set-up. The reference never sees the
program's arrays: after the window it makes the weights again from the
seed, and reads the features from the benchmark's own traffic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.traffic import seed_rngs

REF_BLOCK = 1024    # candidate rows per reference call


def _leaves(tree: dict, prefix: tuple = ()) -> list[tuple[tuple, tuple]]:
    out = []
    for k in sorted(tree):
        v = tree[k]
        if isinstance(v, dict):
            out.extend(_leaves(v, prefix + (k,)))
        else:
            out.append((prefix + (k,), tuple(v)))
    return out


def _nest(pairs) -> dict:
    out: dict = {}
    for path, value in pairs:
        d = out
        for k in path[:-1]:
            d = d.setdefault(k, {})
        d[path[-1]] = value
    return out


def weight_key(seed: int):
    """A JAX key from a run seed of any size."""
    words = seed_rngs(seed, 1)[0].integers(0, 2**32, 2, dtype=np.uint32)
    key = jax.random.PRNGKey(0)
    for w in words:
        key = jax.random.fold_in(key, np.uint32(w))
    return key


def make_weights(shapes: dict, init: dict, seed: int) -> dict:
    """Every weight leaf, made on the device in one jitted call: dense
    kernels ``w`` Glorot-uniform, biases ``b`` normal with ``bias_std``,
    embedding tables normal with ``embedding_std``."""
    leaves = _leaves(shapes)

    def make(key):
        out = []
        for i, (path, shape) in enumerate(leaves):
            k = jax.random.fold_in(key, i)
            leaf = path[-1]
            if leaf == "w":
                lim = float(np.sqrt(6.0 / (shape[0] + shape[-1])))
                v = jax.random.uniform(k, shape, jnp.float32, -lim, lim)
            elif leaf == "b":
                v = jax.random.normal(k, shape, jnp.float32) * init["bias_std"]
            elif leaf == "table":
                v = (jax.random.normal(k, shape, jnp.float32)
                     * init["embedding_std"])
            else:
                raise ValueError(f"no init rule for leaf {path}")
            out.append(v)
        return out

    values = jax.jit(make)(weight_key(seed))
    return _nest(zip((p for p, _ in leaves), values))


def program_graph(cfg: dict):
    """The served model as the program defines it."""
    from repro import configs
    mod = configs.get_config(cfg["registry"])
    build = mod.smoke_build() if cfg["build"] == "smoke" else mod.BUILD
    built = build()
    return built[0] if isinstance(built, tuple) else built


def check_param_shapes(graph, shapes: dict) -> None:
    """Refuse a program whose model names other weights than the
    configuration's, or gives them other shapes."""
    from repro.graph.executor import init_graph_params
    got = jax.eval_shape(lambda k: init_graph_params(graph, k),
                         jax.random.PRNGKey(0))
    have = {p: tuple(s) for p, s in _leaves(
        jax.tree.map(lambda a: list(a.shape), got,
                     is_leaf=lambda a: hasattr(a, "shape")))}
    want = dict(_leaves(shapes))
    if have != want:
        diff = sorted(set(have.items()) ^ set(want.items()))
        raise SystemExit(f"the program's model does not match the "
                         f"configuration: {diff[:8]}")


def build_service(cfg: dict, graph, weights: dict):
    """A ``RankingService`` serving the configuration under its plan, the
    program's normal path."""
    from repro.serve import RankingService, ServePlan
    plan = ServePlan.preset(cfg["preset"]).evolve(
        cache__max_cached_users=cfg["max_cached_users"])
    svc = RankingService(plan, smoke=cfg["build"] == "smoke")
    svc.register(cfg["registry"], graph=graph, params=weights)
    return svc


def mm_highest(spec, a, b):
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def mm_rounded(dtype):
    """Matmuls whose operands are first rounded to ``dtype``, accumulated
    in float32: the reference at a lower precision."""
    def mm(spec, a, b):
        return mm_highest(spec, a.astype(dtype).astype(jnp.float32),
                          b.astype(dtype).astype(jnp.float32))
    return mm


class Reference:
    """Scores requests with the configuration's plain reference, in blocks
    of ``REF_BLOCK`` candidate rows (one compiled shape)."""

    def __init__(self, ref_module, cfg: dict, weights: dict, mm=mm_highest):
        self.weights = weights
        fn = functools.partial(ref_module.scores, cfg=cfg, mm=mm)
        self._run = jax.jit(lambda p, u, c: fn(p, u, c))

    def __call__(self, user: dict, cand: dict) -> np.ndarray:
        n = next(iter(cand.values())).shape[0]
        pad = -n % REF_BLOCK
        padded = {k: np.concatenate([v, np.repeat(v[-1:], pad, axis=0)])
                  if pad else v for k, v in cand.items()}
        out = []
        with jax.default_matmul_precision("highest"):
            for lo in range(0, n + pad, REF_BLOCK):
                block = {k: v[lo:lo + REF_BLOCK] for k, v in padded.items()}
                out.append(np.asarray(self._run(self.weights, user, block)))
        return np.concatenate(out)[:n]
