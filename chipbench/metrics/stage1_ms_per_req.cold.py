"""Engine: milliseconds of stage 1 (the user-side subgraph, blocking) per
request finished in the window (``StageProfiler`` ``stage1`` total)."""


def read(w):
    if not w["requests"] or not w["counters"]["stage1_calls"]:
        return None
    return w["profile_ms"]["stage1"] / w["requests"]
