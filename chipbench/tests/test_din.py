"""The din configuration's arithmetic against hand-worked values and
against the served model: the kernel sites the trace reduction matches,
the MaRI-form FLOPs per candidate, the tables' padded rows, and the
user-rep bytes its memory reckoning states."""
import json

import numpy as np

from chipbench import spec
from chipbench.tests.conftest import BENCH
from chipbench.tests.test_trace import DIN_SITES


def _din():
    cfg = json.loads((BENCH / "configs" / "din.json").read_text())
    return cfg, spec._module(BENCH / "reference" / "din.py", "test")


def test_kernel_sites_are_the_ones_the_trace_reduction_matches():
    cfg, ref = _din()
    assert ref.kernel_sites(cfg) == DIN_SITES


def test_mari_form_flops_per_candidate_against_hand_worked_values():
    cfg, ref = _din()
    # q (W_q - W_d), q against T, the unit's 80 -> 40 -> 1, Eq. (3)'s
    # sum, mlp_0's candidate rows, mlp_1, logit
    assert ref.stage2_flops_per_candidate(cfg) == (
        2_880 + 288_000 + 640_000 + 8_000 + 3_600 + 19_200 + 32_000 + 160
    ) == 993_840


def test_tables_pad_as_the_served_model_pads_them():
    from repro.models.recsys import pad_vocab
    cfg, ref = _din()
    for v in (128, 65_535, 65_536, 65_537, 10_000_000):
        assert ref.table_rows(v) == pad_vocab(v)
    assert ref.param_shapes(cfg)["item_emb"]["table"] == (10_000_128, 18)


def test_user_reps_are_the_bytes_the_memory_reckoning_states():
    """616,000 B a user of stage-1 reps at the published widths, the
    figure din.json's ``assumed`` gives."""
    from repro import configs
    from repro.core.mari import mari_rewrite
    from repro.core.split import split_two_stage
    cfg, _ = _din()
    graph, _ = configs.get_config(cfg["registry"]).BUILD()
    split = split_two_stage(mari_rewrite(graph,
                                         reparam_attention=True).graph)
    per_user = {k: 4 * int(np.prod(s))
                for k, s in split.boundary_specs.items()}
    assert per_user == {"user_seq_emb": 7_200, "din_attn::u_part": 32_000,
                        "din_attn::T": 576_000, "mlp_0::u": 800}
    assert sum(per_user.values()) == 616_000
    assert any("616,000 B a user" in a for a in cfg["assumed"])
