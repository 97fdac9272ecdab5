"""Plain float32 reference of DIN, the Deep Interest Network (Zhou et al.,
KDD 2018, arXiv:1706.06978): the candidate's embedding weighs each of the
user's behaviours through the local activation unit (Fig. 2), the
behaviours are summed by those weights (Eq. 3), and an MLP scores the
user profile, that interest vector, the candidate and the context.

Written from the published description, not from the program: it imports
nothing of ``repro``, runs the activation unit un-rewritten (per candidate
and behaviour, the MLP over the concatenated features, as a trained model
would be run), and reads the weights by layer name. Each matmul goes
through ``mm``, which the benchmark gives at HIGHEST precision for the
reference and with rounded operands for the lower-precision control.

Eq. (3): v_U(A) = sum_j a(e_j, v_A) e_j, where a(.) is the activation
unit's output itself. The paper drops the softmax on purpose (section
4.3), so the weights need not sum to 1.

Departures from the paper, all shared with the served model:
- the activation unit's input is [k, q, k - q, k * q] (behaviour, candidate,
  difference, product), in that order; the paper draws both embeddings
  and their out product;
- activations are ReLU where the paper uses PReLU or Dice;
- one logit where the paper has a two-way softmax;
- behaviours and candidates are looked up in two tables, each padded to a
  multiple of 256 rows above 65,536 rows as the served model pads them
  (``table_rows``); the traffic draws no padding row;
- every behaviour slot is filled, so there is no mask.
"""
import jax
import jax.numpy as jnp

PAD_ROWS, PAD_ABOVE = 256, 65536


def table_rows(vocab):
    """Rows of an embedding table over ``vocab`` ids, padding included."""
    return vocab if vocab < PAD_ABOVE else -(-vocab // PAD_ROWS) * PAD_ROWS


def param_shapes(cfg):
    """Every weight as the served model names it: {layer: {leaf: shape}}."""
    d, rows = cfg["embed_dim"], table_rows(cfg["item_vocab"])

    def dense(din, dout):
        return {"w": (din, dout), "b": (dout,)}

    p = {"user_seq_emb": {"table": (rows, d)},
         "item_emb": {"table": (rows, d)}, "din_attn": {}}
    din = 4 * d
    for li, w in enumerate(list(cfg["attn_mlp"]) + [1]):
        p["din_attn"][f"layer_{li}"] = dense(din, w)
        din = w
    din = cfg["user_profile_dim"] + 2 * d + cfg["context_dim"]
    for li, w in enumerate(cfg["mlp"]):
        p[f"mlp_{li}"] = dense(din, w)
        din = w
    p["logit"] = dense(din, 1)
    return p


def input_specs(cfg):
    """The request's features: name -> (domain, per-row shape, dtype)."""
    return {"user_profile": ("user", (cfg["user_profile_dim"],), "float32"),
            "user_seq_ids": ("user", (cfg["seq_len"],), "int32"),
            "item_ids": ("item", (), "int32"),
            "cross_context": ("cross", (cfg["context_dim"],), "float32")}


def scores(params, user, cand, cfg, mm):
    """(B, 1) logits for one user's (1, ...) features and B candidate
    rows."""
    def dense(p, x, act=True):
        y = mm("...i,io->...o", x, p["w"]) + p["b"]
        return jax.nn.relu(y) if act else y

    q = jnp.take(params["item_emb"]["table"], cand["item_ids"], axis=0)
    keys = jnp.take(params["user_seq_emb"]["table"], user["user_seq_ids"][0],
                    axis=0)                                   # (L, D)
    b, (l, d) = q.shape[0], keys.shape
    k = jnp.broadcast_to(keys[None], (b, l, d))
    qb = jnp.broadcast_to(q[:, None, :], (b, l, d))
    unit = params["din_attn"]
    h = jnp.concatenate([k, qb, k - qb, k * qb], -1)          # (B, L, 4D)
    for li in range(len(cfg["attn_mlp"])):
        h = dense(unit[f"layer_{li}"], h)
    a = dense(unit[f"layer_{len(cfg['attn_mlp'])}"], h, act=False)[..., 0]
    interest = mm("bl,ld->bd", a, keys)                       # Eq. (3)

    profile = jnp.broadcast_to(user["user_profile"],
                               (b,) + user["user_profile"].shape[1:])
    h = jnp.concatenate([profile, interest, q, cand["cross_context"]], -1)
    for li in range(len(cfg["mlp"])):
        h = dense(params[f"mlp_{li}"], h)
    return dense(params["logit"], h, act=False)


def stage2_flops_per_candidate(cfg):
    """Matmul FLOPs per candidate of the MaRI form the program serves: the
    user side runs once per user and is not counted. The activation unit's
    first layer splits as [k, q, k - q, k * q] W = k (W_k + W_d) + q (W_q -
    W_d) + (k * q) W_p, and only the candidate's terms are counted. At the
    configuration's widths (D 18, L 100, unit 80-40, MLP 200-80, context
    12):

        q (W_q - W_d)            2 * 18 * 80           =   2,880
        (k * q) W_p, as q.T      2 * 100 * 18 * 80     = 288,000
        unit 80 -> 40            2 * 100 * 80 * 40     = 640,000
        unit 40 -> 1             2 * 100 * 40          =   8,000
        Eq. (3)'s weighted sum   2 * 100 * 18          =   3,600
        mlp_0, candidate rows    2 * (18 + 18 + 12) * 200 = 19,200
        mlp_1                    2 * 200 * 80          =  32,000
        logit                    2 * 80                =     160
                                                       = 993,840
    """
    d, l, units = cfg["embed_dim"], cfg["seq_len"], list(cfg["attn_mlp"])
    f = 2 * d * units[0] + 2 * l * d * units[0]
    for a, b in zip(units, units[1:] + [1]):
        f += 2 * l * a * b
    f += 2 * l * d
    din = 2 * d + cfg["context_dim"]           # mlp_0's candidate rows
    for w in cfg["mlp"]:
        f += 2 * din * w
        din = w
    return f + 2 * din


def kernel_sites(cfg):
    """Where the stage-2 kernels run: the candidate-side (K, N) of the
    MLP's first layer, which MaRI splits (``mari_matmul``), and the
    activation unit's per-user table (L, D, H) that the candidate is
    contracted against (``gather_einsum``)."""
    d = cfg["embed_dim"]
    return {"mari_matmul": [("mlp_0", 2 * d + cfg["context_dim"],
                             cfg["mlp"][0])],
            "gather_einsum": [("din_attn", cfg["seq_len"], d,
                               cfg["attn_mlp"][0])]}
