"""Graph executor: interprets a repro.graph IR under jit.

Batch semantics — the key to VanI / UOI / MaRI:

* Every feed carries a leading batch dim. Item/cross feeds arrive at B
  (candidate count); user feeds arrive at 1.
* ``vani`` mode tiles user feeds to B at entry — the whole graph runs at B
  (training-identical computation, fully redundant user side).
* ``uoi`` mode keeps user feeds at 1. Batch-1-ness propagates through the
  user-only subgraph automatically; the first op that mixes batch-1 with
  batch-B inputs (a concat, an add, an attention) broadcasts — that IS the
  deferred tile of Fig. 1(c).
* ``mari`` is not a mode here: the MaRI pass rewrites eligible ``dense``
  nodes into ``mari_dense`` nodes (repro.core.mari) and the rewritten graph
  runs in ``uoi`` mode — the tile is deferred *through* the matmul (Eq. 7).
* **row-wise user values** — user-side feeds (raw inputs, stage-2 boundary
  activations, rewritten-unit partials) may also arrive at batch B, where
  row b carries user b's value (a cross-user coalesced serving batch,
  gathered by ``reps[user_index]`` upstream). Every op dispatches on the
  leading dim: batch-1 operands take the broadcast (deferred-tile) forms,
  batch-B operands the row-wise forms; results are row-identical either
  way.
"""
from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp

from repro.common import Array, KeySeq, glorot, normal_init
from repro.graph.ir import Graph, Node, infer_shapes
from repro.nn.layers import ACTIVATIONS
from repro.nn.attention import cross_attention

# Reserved feed key: per-candidate-row user index for kernel-side gather.
# When present, input nodes listed in ``Executor.lazy_gather_inputs``
# receive their STACKED (U, ...) rep table as the fed value and the gather
# moves into the consuming kernel: the Pallas mari_matmul indexes the
# (U, units) table at accumulator-init load, and the decomposed-attention
# contractions run through ``kernels.gather_einsum`` — the gathered
# (B, units) / (B, L, D, h) blocks never materialize. Out-of-range indices
# (padded batch rows) clamp everywhere (``mode="clip"``): they read a real
# user's reps instead of wrapping or going NaN, and their rows are sliced
# off by the serving engine like every other padded row.
USER_INDEX_FEED = "__user_index__"


def init_graph_params(graph: Graph, key, dtype=jnp.float32) -> dict:
    """Initialize params for every parameterized node."""
    ks = KeySeq(key)
    shapes = infer_shapes(graph)
    params: dict = {}
    for n in graph.topo_order():
        if n.op == "dense":
            din = shapes[n.inputs[0]][-1]
            p = {"w": glorot(next(ks), (din, n.attrs["units"]), dtype)}
            if n.attrs.get("use_bias", True):
                p["b"] = jnp.zeros((n.attrs["units"],), dtype)
            params[n.name] = p
        elif n.op == "embedding":
            scale = 1.0 / max(n.attrs["vocab"], 1) ** 0.5
            params[n.name] = {
                "table": normal_init(next(ks), (n.attrs["vocab"], n.attrs["dim"]),
                                     scale, dtype)}
        elif n.op == "target_attention":
            d = shapes[n.inputs[0]][-1]
            dims = (4 * d,) + tuple(n.attrs["mlp_hidden"]) + (1,)
            p = {}
            for li, (di, do) in enumerate(zip(dims[:-1], dims[1:])):
                p[f"layer_{li}"] = {"w": glorot(next(ks), (di, do), dtype),
                                    "b": jnp.zeros((do,), dtype)}
            if n.attrs.get("decomposed"):
                # re-parameterized unit (core.mari.AttnRewrite): split blocks
                h1 = n.attrs["mlp_hidden"][0]
                p["layer_0"] = {
                    "w_kd": glorot(next(ks), (d, h1), dtype),
                    "w_qd": glorot(next(ks), (d, h1), dtype),
                    "w_p": glorot(next(ks), (d, h1), dtype),
                    "b": jnp.zeros((h1,), dtype)}
            params[n.name] = p
        elif n.op == "mari_dense":
            # Normally produced by repro.core.mari.convert_params; direct init
            # creates the already-split blocks.
            units = n.attrs["units"]
            p = {}
            for label, seg_idx in n.attrs["groups"]:
                d = sum(n.attrs["seg_widths"][i] for i in seg_idx)
                p[f"w_{label}"] = glorot(next(ks), (d, units), dtype)
            if n.attrs.get("use_bias", True):
                p["b"] = jnp.zeros((units,), dtype)
            params[n.name] = p
    return params


def _bcast_batch(xs: list[Array]) -> list[Array]:
    """Broadcast leading batch dims (1 -> B) across a list of arrays."""
    b = max(x.shape[0] for x in xs)
    out = []
    for x in xs:
        if x.shape[0] != b:
            x = jnp.broadcast_to(x, (b,) + x.shape[1:])
        out.append(x)
    return out


def _concat_xs(xs: list[Array]) -> Array:
    xs = _bcast_batch(xs) if len({x.shape[0] for x in xs}) > 1 else xs
    return jnp.concatenate(xs, axis=-1) if len(xs) > 1 else xs[0]


def _concat_ws(ws: list[Array]) -> Array:
    return jnp.concatenate(ws, axis=0) if len(ws) > 1 else ws[0]


def _mari_dense_operands(node: Node, params: dict, vals: dict):
    """Assemble (x, w) pairs + accumulator init + bias for a ``mari_dense``.

    Returns (parts, acc0, bias): ``parts`` is a list of (x, w) whose products
    sum to the pre-activation output (minus acc0/bias); ``acc0`` is a
    precomputed user partial — a (1, units) row, or a row-wise (B, units)
    block when stage 2 serves a cross-user coalesced batch — or None;
    ``bias`` is the bias vector or None.

    The batched (non-user) groups are fused into ONE (x, w) stream via the
    block-matmul identity Σ_g x_g W_g == concat(x_g) @ stack(W_g) — matching
    the Pallas kernel's single MXU stream. When the serving engine has
    pre-concatenated the grouped weights at build time (``w_cat`` in the
    node's params), the per-call weight concat disappears from the hot path;
    either way the streamed operands are identical, so scores are
    bit-identical with pre-concat on or off.
    """
    attrs = node.attrs
    p = params[node.name]
    cast = attrs.get("cast_dtype")

    def seg(name: str) -> Array:
        x = vals[name]
        return x.astype(cast) if cast else x

    parts: list[tuple[Array, Array]] = []
    acc0 = vals[node.inputs[0]] if attrs.get("precomputed_user") else None
    if attrs.get("fragment", False):
        if acc0 is not None:
            # Stage-2 residual of a split fragmented node: every remaining
            # segment is candidate-side — fuse them into one stream instead
            # of paying the Table-3 per-fragment launches while serving.
            x = _concat_xs([seg(nm) for nm in node.inputs[1:]])
            w = p.get("w_cat")
            if w is None:
                w = _concat_ws([p[f"w_seg{i}"]
                                for i in attrs["seg_param_idx"]])
            parts.append((x, w))
        else:
            # Table-3 regime: one small matmul per original concat segment
            # (batch-1-ness varies per segment, so no static fusion).
            for i, name in enumerate(node.inputs):
                parts.append((seg(name), p[f"w_seg{i}"]))
    else:
        # "groups" indices already point into node.inputs on both paths (the
        # split pass remaps them past the partial at position 0). The user
        # group (present only when un-peeled) stays its own one-shot part;
        # all other groups fuse into a single batched stream.
        rest_xs: list[Array] = []
        rest_ws: list[Array] = []
        for label, seg_idx in attrs["groups"]:
            if label == "user":
                parts.append((_concat_xs([seg(node.inputs[i])
                                          for i in seg_idx]), p["w_user"]))
            else:
                rest_xs.extend(seg(node.inputs[i]) for i in seg_idx)
                rest_ws.append(p[f"w_{label}"])
        if rest_xs:
            w = p.get("w_cat")
            if w is None:
                w = _concat_ws(rest_ws)
            parts.append((_concat_xs(rest_xs), w))
    bias = p["b"] if attrs.get("use_bias", True) else None
    return parts, acc0, bias


def _run_mari_dense(node: Node, params: dict, vals: dict, *,
                    use_pallas: bool = False, interpret: bool = False,
                    user_index: Array | None = None) -> Array:
    """Eq. 7: Tile(Σ_user x_u W_u, B) + Σ_rest x W  — tile realized as a
    broadcast add (never materialized).

    With ``use_pallas`` the batched side dispatches to the fused Pallas
    kernel (``kernels.mari_matmul``): user row as accumulator init, bias and
    activation applied in the kernel epilogue, so the (B, units)
    pre-activation never round-trips through HBM. With ``user_index`` the
    precomputed partial arrives as a stacked (U, units) table and the
    kernel gathers row ``user_index[b]`` at accumulator-init load time
    (bit-identical: gather commutes with the elementwise epilogue).
    """
    attrs = node.attrs
    parts, acc0, bias = _mari_dense_operands(node, params, vals)
    activation = attrs.get("activation", "identity")
    if use_pallas:
        from repro.kernels.mari_matmul import mari_matmul_fused_groups
        return mari_matmul_fused_groups(parts, bias, acc0=acc0,
                                        user_index=user_index,
                                        activation=activation,
                                        interpret=interpret)
    if user_index is not None and acc0 is not None:
        # jnp fallback: explicit gather; clip so a padded row's index can
        # never wrap to an arbitrary slot or NaN-poison the row
        acc0 = jnp.take(acc0, user_index, axis=0, mode="clip")
    acc = acc0
    for x, w in parts:
        y = x @ w
        acc = y if acc is None else acc + y  # (1,u) + (B,u) broadcasts
    if bias is not None:
        acc = acc + bias
    return ACTIVATIONS[activation](acc)


class Executor:
    """Interpret a graph. Construct once, then jit ``run``."""

    def __init__(self, graph: Graph, mode: str = "uoi", *,
                 use_pallas: bool = False, pallas_interpret: bool | None = None,
                 kernel_gather: bool = False, gather_attention: bool = False):
        if mode not in ("vani", "uoi"):
            raise ValueError(f"mode must be 'vani' or 'uoi', got {mode!r}")
        self.graph = graph
        self.mode = mode
        # Backend-gated Pallas dispatch: compiled on TPU, the interpreter on
        # CPU (validation). Any other backend would silently run the
        # interpreter in place of the device, so it is refused.
        self.use_pallas = use_pallas
        if pallas_interpret is None:
            backend = jax.default_backend()
            if use_pallas and backend not in ("cpu", "tpu"):
                raise ValueError(
                    f"use_pallas on backend {backend!r}: the Pallas kernels "
                    f"compile for 'tpu' and run interpreted only on 'cpu'")
            pallas_interpret = backend == "cpu"
        self.pallas_interpret = pallas_interpret
        self.gather_attention = gather_attention
        self._user_inputs = {
            n.name for n in graph.input_nodes() if n.attrs.get("domain") == "user"
        }
        # Gather-at-load: user-side inputs whose EVERY consumption is
        # gather-capable may be fed as stacked (U, ...) rep tables + a
        # USER_INDEX_FEED row index, and the consuming op indexes the table
        # inside its contraction instead of receiving a pre-gathered
        # row-wise value. Two consumer kinds qualify:
        #
        # * a Pallas ``mari_dense`` accumulator init (``kernel_gather``):
        #   the kernel gathers the (U, units) table at acc-init load;
        # * a decomposed+precomputed ``target_attention`` operand
        #   (``gather_attention``): keys / u_part / T (and the mask) are
        #   indexed by ``kernels.gather_einsum`` inside the attention
        #   contractions, so the (B, L, D, h)-class gathered blocks never
        #   materialize.
        #
        # Any other consumer needs the materialized row-wise value, so such
        # inputs stay on the explicit-gather path.
        self.lazy_gather_inputs: frozenset[str] = frozenset()
        allow_md = kernel_gather and use_pallas
        if allow_md or gather_attention:
            lazy = set()
            for n in graph.input_nodes():
                if n.attrs.get("domain") != "user":
                    continue
                cons = graph.consumers(n.name)
                if cons and all(
                        (allow_md and self._is_md_acc_init(c, n.name))
                        or (gather_attention
                            and self._is_attn_operand(c, n.name))
                        for c in cons):
                    lazy.add(n.name)
            self.lazy_gather_inputs = frozenset(lazy)

    @staticmethod
    def _is_md_acc_init(c: Node, name: str) -> bool:
        """``name`` feeds ``c`` only as a Pallas-eligible mari_dense
        accumulator init (the mixed-precision path keeps jnp)."""
        return (c.op == "mari_dense"
                and c.attrs.get("precomputed_user")
                and not c.attrs.get("cast_dtype")
                and c.inputs[0] == name
                and c.inputs.count(name) == 1)

    @staticmethod
    def _is_attn_operand(c: Node, name: str) -> bool:
        """``name`` feeds ``c`` only in gather-capable positions of a
        decomposed, precomputed target_attention: keys (1), u_part (-2),
        T (-1), and the mask (2) when present. The query (0) is
        candidate-side by construction and never qualifies."""
        if not (c.op == "target_attention" and c.attrs.get("decomposed")
                and c.attrs.get("precomputed")):
            return False
        k = len(c.inputs)
        allowed = {1, k - 2, k - 1}
        if c.attrs.get("has_mask"):
            allowed.add(2)
        return all(i in allowed
                   for i, s in enumerate(c.inputs) if s == name)

    def run(self, params: dict, feeds: Mapping[str, Array]) -> dict[str, Array]:
        vals: dict[str, Array] = {}
        if USER_INDEX_FEED in feeds:
            vals[USER_INDEX_FEED] = feeds[USER_INDEX_FEED]
        batch = max((v.shape[0] for k, v in feeds.items()
                     if k not in self._user_inputs and k != USER_INDEX_FEED),
                    default=1)
        for n in self.graph.topo_order():
            vals[n.name] = self._eval(n, params, vals, feeds, batch)
        return {o: vals[o] for o in self.graph.outputs}

    def __call__(self, params, feeds):
        return self.run(params, feeds)

    def _gather_einsum(self, spec, x, table, uidx) -> Array:
        """Contract ``x`` against the stacked ``(U, ...)`` table, indexed
        per row by ``uidx`` — Pallas kernel when enabled, jnp.take oracle
        otherwise (bit-identical semantics; only the memory profile
        differs)."""
        if self.use_pallas:
            from repro.kernels.gather_einsum import gather_einsum
            return gather_einsum(spec, x, table, uidx,
                                 interpret=self.pallas_interpret)
        from repro.kernels.gather_einsum import gather_einsum_ref
        return gather_einsum_ref(spec, x, table, uidx)

    # ------------------------------------------------------------------
    def _eval(self, n: Node, params, vals, feeds, batch: int) -> Array:
        op = n.op
        if op == "input":
            x = feeds[n.name]
            if (self.mode == "vani" and n.name in self._user_inputs
                    and x.shape[0] == 1 and batch > 1):
                x = jnp.broadcast_to(x, (batch,) + x.shape[1:])
            return x
        ins = [vals[i] for i in n.inputs]
        if op == "dense":
            p = params[n.name]
            y = ins[0] @ p["w"]
            if n.attrs.get("use_bias", True):
                y = y + p["b"]
            return ACTIVATIONS[n.attrs.get("activation", "identity")](y)
        if op == "mari_dense":
            # The Pallas path requires a clean f32 pipeline; mixed-precision
            # (cast_dtype) nodes keep the jnp path.
            use_pallas = self.use_pallas and not n.attrs.get("cast_dtype")
            uidx = (vals.get(USER_INDEX_FEED)
                    if n.inputs and n.inputs[0] in self.lazy_gather_inputs
                    else None)
            return _run_mari_dense(n, params, vals, use_pallas=use_pallas,
                                   interpret=self.pallas_interpret,
                                   user_index=uidx)
        if op == "mari_user_partial":
            # Stage-1 half of a split mari_dense: Σ_user x_u W_u (+ b), a
            # (1, units) row the batched stage consumes as accumulator init.
            p = params[n.attrs["param_of"]]
            cast = n.attrs.get("cast_dtype")
            if n.attrs.get("fragment"):
                acc = None
                for i, name in zip(n.attrs["seg_idx"], n.inputs):
                    x = vals[name]
                    if cast:
                        x = x.astype(cast)
                    y = x @ p[f"w_seg{i}"]
                    acc = y if acc is None else acc + y
            else:
                xs = [vals[i] for i in n.inputs]
                x = jnp.concatenate(xs, axis=-1) if len(xs) > 1 else xs[0]
                if cast:
                    x = x.astype(cast)
                acc = x @ p["w_user"]
            if n.attrs.get("use_bias", True) and "b" in p:
                acc = acc + p["b"]
            return acc
        if op == "attn_user_part":
            # One-shot k @ w_kd (+ b) of a decomposed target_attention.
            l0 = params[n.attrs["param_of"]]["layer_0"]
            return (ins[0][0] @ l0["w_kd"] + l0["b"])[None]
        if op == "attn_user_T":
            # One-shot T[l,d,h] = k[l,d] * w_p[d,h].
            l0 = params[n.attrs["param_of"]]["layer_0"]
            return (ins[0][0][:, :, None] * l0["w_p"][None])[None]
        if op == "embedding":
            rows = jnp.take(params[n.name]["table"], ins[0], axis=0)
            pool = n.attrs.get("pool")
            if pool == "sum":
                rows = rows.sum(axis=-2)
            elif pool == "mean":
                rows = rows.mean(axis=-2)
            return rows
        if op == "concat":
            xs = _bcast_batch(ins)
            return jnp.concatenate(xs, axis=n.attrs.get("axis", -1))
        if op == "add":
            return ins[0] + ins[1]
        if op == "mul":
            return ins[0] * ins[1]
        if op == "sub":
            return ins[0] - ins[1]
        if op == "scale":
            return ins[0] * n.attrs["factor"]
        if op == "target_attention":
            from repro.nn.attention import target_attention as _ta
            from repro.nn.layers import dense_apply
            p = params[n.name]
            nlayers = len(p)
            q, keys = ins[0], ins[1]
            if n.attrs.get("has_mask"):
                mask = ins[2]
            else:
                mask = jnp.ones(keys.shape[:-1], bool)

            if n.attrs.get("decomposed") and "w_kd" in p["layer_0"]:
                # Beyond-paper re-parameterized unit (core.mari.AttnRewrite).
                # The user-side tensors carry batch 1 (one user per batch —
                # the (B, L, 4D) feature tensor never materializes and the
                # broadcast einsums realize the deferred tile) OR batch B
                # (row-wise: a cross-user coalesced batch where row b holds
                # user b's gathered tensors) OR — gather-aware serving —
                # arrive as stacked (U, ...) rep tables alongside a
                # USER_INDEX_FEED, in which case the per-row gather folds
                # into the contractions (kernels.gather_einsum) and the
                # (B, L, D, h)-class gathered blocks never materialize.
                l0 = p["layer_0"]
                uidx = vals.get(USER_INDEX_FEED)

                def stacked(name: str) -> bool:
                    return uidx is not None and name in self.lazy_gather_inputs

                t_stacked = u_stacked = k_stacked = False
                if n.attrs.get("precomputed"):
                    # Two-stage serving: one-shot tensors arrive from stage 1
                    # (core.split) — bias is folded into u_part there.
                    u_part = ins[-2]                    # (1|B|U, L, h)
                    t = ins[-1]                         # (1|B|U, L, D, h)
                    u_stacked = stacked(n.inputs[-2])
                    t_stacked = stacked(n.inputs[-1])
                    k_stacked = stacked(n.inputs[1])
                else:
                    if keys.shape[0] == 1:
                        u_part = (keys[0] @ l0["w_kd"] + l0["b"])[None]
                        t = (keys[0][:, :, None] * l0["w_p"][None])[None]
                    else:                               # row-wise keys
                        u_part = keys @ l0["w_kd"] + l0["b"]
                        t = keys[..., None] * l0["w_p"][None, None]
                if n.attrs.get("has_mask") and stacked(n.inputs[2]):
                    mask = jnp.take(mask, uidx, axis=0, mode="clip")
                elif not n.attrs.get("has_mask") and k_stacked:
                    # the default all-ones mask above took its shape from
                    # the STACKED keys (U, L): re-shape to broadcast (1, L)
                    mask = jnp.ones((1,) + keys.shape[1:-1], bool)
                q_part = q @ l0["w_qd"]                 # (B, h)
                if t_stacked:
                    p_part = self._gather_einsum("bd,uldh->blh", q, t, uidx)
                elif t.shape[0] == 1 and q.shape[0] != 1:
                    p_part = jnp.einsum("bd,ldh->blh", q, t[0])
                else:
                    p_part = jnp.einsum("bd,bldh->blh", q, t)
                if u_stacked:
                    # (B, L, h) exists anyway as the relu output below, so
                    # an explicit (clamped) gather costs nothing extra
                    u_part = jnp.take(u_part, uidx, axis=0, mode="clip")
                h = jax.nn.relu(u_part + q_part[:, None, :] + p_part)
                for li in range(1, nlayers):
                    h = dense_apply(p[f"layer_{li}"], h)
                    if li < nlayers - 1:
                        h = jax.nn.relu(h)
                # Eq. (3): the unit's outputs are the pooling weights
                w = jnp.where(mask, h[..., 0], 0.0)     # (B, L)
                if k_stacked:
                    return self._gather_einsum("bl,uld->bd", w, keys, uidx)
                if keys.shape[0] == 1 and w.shape[0] != 1:
                    return jnp.einsum("bl,ld->bd", w, keys[0])
                return jnp.einsum("bl,bld->bd", w, keys)

            def mlp_apply(x):
                for li in range(nlayers):
                    x = dense_apply(p[f"layer_{li}"], x)
                    if li < nlayers - 1:
                        x = jax.nn.relu(x)
                return x

            return _ta(q, keys, mask, mlp_apply)
        if op == "act":
            return ACTIVATIONS[n.attrs["fn"]](ins[0])
        if op == "softmax":
            return jax.nn.softmax(ins[0], axis=n.attrs.get("axis", -1))
        if op == "reshape":
            return ins[0].reshape((ins[0].shape[0],) + tuple(n.attrs["shape"]))
        if op == "cast":
            return ins[0].astype(n.attrs["dtype"])
        if op in ("identity", "stop_gradient"):
            return jax.lax.stop_gradient(ins[0]) if op == "stop_gradient" else ins[0]
        if op == "reduce":
            fn = {"sum": jnp.sum, "mean": jnp.mean, "max": jnp.max}[n.attrs["fn"]]
            return fn(ins[0], axis=n.attrs["axis"])
        if op == "weighted_sum":
            w, v = ins
            if w.shape[0] != v.shape[0]:
                w, v = _bcast_batch([w, v])
            return jnp.einsum("...k,...kd->...d", w, v)
        if op == "cross_attention":
            q, k, v = ins[0], ins[1], ins[2]
            mask = ins[3] if n.attrs.get("has_mask") else None
            squeeze = q.ndim == 2
            if squeeze:
                q = q[:, None, :]
            out = cross_attention(q, k, v, mask)
            return out[:, 0, :] if squeeze else out
        if op == "fm_interaction":
            x = ins[0]
            s = x.sum(axis=-2)
            sq = (x * x).sum(axis=-2)
            return (0.5 * (s * s - sq).sum(axis=-1))[..., None]
        if op == "dot_interaction":
            x = ins[0]
            f = x.shape[-2]
            z = jnp.einsum("...fd,...gd->...fg", x, x)
            iu, ju = jnp.triu_indices(f, k=0 if n.attrs.get("keep_self") else 1)
            return z[..., iu, ju]
        if op == "gather_last":
            idx = jnp.asarray(n.attrs["indices"], jnp.int32)
            return jnp.take(ins[0], idx, axis=-1)
        if op == "stack_features":
            xs = _bcast_batch(ins)
            return jnp.stack(xs, axis=-2)
        raise ValueError(f"executor: unknown op {op!r} ({n.name})")
