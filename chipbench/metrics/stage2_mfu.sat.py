"""Whole stage-2 step: share of the chip's bf16 peak, in %, that the
candidates scored in the traced stretch represent at the paper's MaRI-form
FLOPs per candidate (the reference module's
``stage2_flops_per_candidate``, fixed whatever implements it)."""


def read(w):
    t = w["trace"]
    if not t or not t.get("devices") or not t.get("candidates"):
        return None
    flops = t["candidates"] * w["flops_per_candidate"]
    return 100.0 * flops / (t["window_s"] * w["peak"]["bf16_flops_per_s"])
