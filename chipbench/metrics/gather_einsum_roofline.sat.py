"""Kernels: share of its roofline that the gather-einsum kernel (DIN's
activation unit contracted against stacked per-user tables) reaches over
the traced stretch, in %: the least time the chip needs for the work of
every ``gather_einsum_kernel`` event (``counts.py``: the configuration's
L, D and H, the event's rows, padding rows of the pack's bucket included)
/ the events' summed device time."""


def read(w):
    k = (w["trace"] or {}).get("kernels", {}).get("gather_einsum")
    if not k or not k["seconds"] or not k["min_seconds"]:
        return None
    return 100.0 * k["min_seconds"] / k["seconds"]
