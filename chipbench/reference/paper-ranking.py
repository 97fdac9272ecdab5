"""Plain float32 reference of the MaRI paper's coarse-ranking model
(arXiv:2602.23105, Fig. 1): user tower, candidate-to-behaviour cross
attention, MMoE over the fused features, one tower per task.

Written from the published description, not from the program: it imports
nothing of ``repro``, computes every layer un-rewritten (each candidate row
carries the user's values, concatenated before the dense layer, as a
trained model would be run), and reads the weights by layer name. Each
matmul goes through ``mm``, which the benchmark gives at HIGHEST precision
for the reference and with rounded operands for the lower-precision
control.

Departures from the paper, all shared with the served model:
- the paper names the layers but not the order of concatenated inputs; the
  order is [item, user context] for the query, [user tower, attention,
  item, cross] for the MMoE input and [expert mix, user tower] for each
  task tower, and the weights' rows follow it;
- activations are ReLU throughout, and the gates a softmax over experts;
- the cross attention is single-head, scaled by 1/sqrt(d_attn), with no
  mask (every behaviour slot is filled).
"""
import jax
import jax.numpy as jnp


def param_shapes(cfg):
    """Every weight as the served model names it: {layer: {leaf: shape}}."""
    du, di, dc = cfg["d_user_profile"], cfg["d_item"], cfg["d_cross"]
    da, dt, ds = cfg["d_attn"], cfg["d_user_tower"], cfg["d_seq"]
    d_fusion = dt + da + di + dc

    def dense(din, dout, bias=True):
        return {"w": (din, dout), **({"b": (dout,)} if bias else {})}

    p = {"user_tower_fc1": dense(du, dt), "user_tower_fc2": dense(dt, dt),
         "attn_k_proj": dense(ds, da, False),
         "attn_v_proj": dense(ds, da, False),
         "user_ctx_proj": dense(du, da),
         "attn_q_proj": dense(di + da, da, False)}
    for e in range(cfg["n_experts"]):
        din = d_fusion
        for li, w in enumerate(cfg["d_expert"]):
            p[f"expert{e}_fc{li}"] = dense(din, w)
            din = w
    for t in range(cfg["n_tasks"]):
        p[f"gate{t}_proj"] = dense(d_fusion, cfg["n_experts"])
        din = cfg["d_expert"][-1] + dt
        for li, w in enumerate(cfg["d_tower"]):
            p[f"task{t}_fc{li}"] = dense(din, w)
            din = w
        p[f"task{t}_logit"] = dense(din, 1)
    return p


def input_specs(cfg):
    """The request's features: name -> (domain, per-row shape, dtype)."""
    return {"user_profile": ("user", (cfg["d_user_profile"],), "float32"),
            "user_seq": ("user", (cfg["seq_len"], cfg["d_seq"]), "float32"),
            "item_feats": ("item", (cfg["d_item"],), "float32"),
            "cross_feats": ("cross", (cfg["d_cross"],), "float32")}


def scores(params, user, cand, cfg, mm):
    """(B, n_tasks) logits for one user's (1, ...) features and B
    candidate rows."""
    def dense(name, x, act=True):
        p = params[name]
        y = mm("...i,io->...o", x, p["w"])
        if "b" in p:
            y = y + p["b"]
        return jax.nn.relu(y) if act else y

    item, cross = cand["item_feats"], cand["cross_feats"]
    b = item.shape[0]
    tile = lambda x: jnp.broadcast_to(x, (b,) + x.shape[1:])
    profile = tile(user["user_profile"])
    seq = tile(user["user_seq"])

    u_emb = dense("user_tower_fc2", dense("user_tower_fc1", profile))
    k = dense("attn_k_proj", seq, act=False)
    v = dense("attn_v_proj", seq, act=False)
    u_ctx = dense("user_ctx_proj", profile)
    q = dense("attn_q_proj", jnp.concatenate([item, u_ctx], -1), act=False)
    logits = mm("bd,bld->bl", q, k) / jnp.sqrt(jnp.float32(cfg["d_attn"]))
    e_iu = mm("bl,bld->bd", jax.nn.softmax(logits, axis=-1), v)
    fusion = jnp.concatenate([u_emb, e_iu, item, cross], -1)

    experts = []
    for e in range(cfg["n_experts"]):
        h = fusion
        for li in range(len(cfg["d_expert"])):
            h = dense(f"expert{e}_fc{li}", h)
        experts.append(h)
    experts = jnp.stack(experts, axis=-2)
    out = []
    for t in range(cfg["n_tasks"]):
        gate = jax.nn.softmax(dense(f"gate{t}_proj", fusion, act=False), -1)
        h = jnp.concatenate([mm("be,bed->bd", gate, experts), u_emb], -1)
        for li in range(len(cfg["d_tower"])):
            h = dense(f"task{t}_fc{li}", h)
        out.append(dense(f"task{t}_logit", h, act=False))
    return jnp.concatenate(out, -1)


def stage2_flops_per_candidate(cfg):
    """Matmul FLOPs per candidate of the paper's MaRI form: the user side
    runs once per user and is not counted; every layer that reads a
    candidate feature counts only its candidate-side rows."""
    da, dt = cfg["d_attn"], cfg["d_user_tower"]
    d_cand = da + cfg["d_item"] + cfg["d_cross"]      # MMoE input, per row
    f = 2 * cfg["d_item"] * da                        # attn_q_proj
    f += 2 * 2 * cfg["seq_len"] * da                  # q.k, p.v
    for _ in range(cfg["n_experts"]):
        din = d_cand
        for w in cfg["d_expert"]:
            f += 2 * din * w
            din = w
    f += cfg["n_tasks"] * 2 * d_cand * cfg["n_experts"]          # gates
    f += cfg["n_tasks"] * 2 * cfg["n_experts"] * cfg["d_expert"][-1]
    for _ in range(cfg["n_tasks"]):
        din = cfg["d_expert"][-1]                     # tower's candidate rows
        for w in cfg["d_tower"]:
            f += 2 * din * w
            din = w
        f += 2 * din
    return f


def kernel_sites(cfg):
    """Candidate-side (K, N) of each dense layer that MaRI splits, where
    the fused matmul kernel runs; attention runs no gather kernel here."""
    d_cand = cfg["d_attn"] + cfg["d_item"] + cfg["d_cross"]
    sites = [("attn_q_proj", cfg["d_item"], cfg["d_attn"])]
    sites += [(f"expert{e}_fc0", d_cand, cfg["d_expert"][0])
              for e in range(cfg["n_experts"])]
    sites += [(f"gate{t}_proj", d_cand, cfg["n_experts"])
              for t in range(cfg["n_tasks"])]
    sites += [(f"task{t}_fc0", cfg["d_expert"][-1], cfg["d_tower"][0])
              for t in range(cfg["n_tasks"])]
    return {"mari_matmul": sites, "gather_einsum": []}
