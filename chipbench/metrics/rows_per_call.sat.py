"""Batcher: candidate rows scored per stage-2 dispatch over the window
(candidates of the requests finished in the window / ``stage2_calls``)."""


def read(w):
    calls = w["counters"]["stage2_calls"]
    return w["candidates"] / calls if calls else None
