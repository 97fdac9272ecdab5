"""Kernels: share of its roofline that the fused MaRI matmul kernel
reaches over the traced stretch, in %: the least time the chip needs for
the work of every ``mari_matmul_kernel`` event (``counts.py``: the
configuration's K and N, the event's rows, padding rows of the pack's
bucket included) / the events' summed device time."""


def read(w):
    k = (w["trace"] or {}).get("kernels", {}).get("mari_matmul")
    if not k or not k["seconds"] or not k["min_seconds"]:
        return None
    return 100.0 * k["min_seconds"] / k["seconds"]
