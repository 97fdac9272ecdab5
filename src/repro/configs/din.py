"""DIN (arXiv:1706.06978): embed 18, behaviour seq 100, attention MLP
80-40, fusion MLP 200-80, user profile 36, context 12, 10M items.

From the paper: the activation unit and Eq. (3)'s unnormalized pooling,
and the fusion MLP's 200-80 (its Alibaba set-up, "192 x 200 x 80 x 2").
Every other width is assumed (the paper embeds 16 feature groups in 12
each and gives no attention-MLP widths, sequence cap or profile width),
and 10M items is one chip's slice of its 0.6 billion goods.
``chipbench/configs/din.json`` lists each under ``described``,
``assumed`` and ``reduced``."""
import functools

from repro.configs._recsys_shapes import RECSYS_SHAPES
from repro.models.recsys import build_din

FAMILY = "recsys"
BUILD = functools.partial(build_din, embed_dim=18, seq_len=100,
                          attn_mlp=(80, 40), mlp=(200, 80),
                          item_vocab=10_000_000)
SHAPES = dict(RECSYS_SHAPES)


def smoke_build():
    return functools.partial(build_din, embed_dim=8, seq_len=12,
                             attn_mlp=(16, 8), mlp=(24, 12), item_vocab=128)
