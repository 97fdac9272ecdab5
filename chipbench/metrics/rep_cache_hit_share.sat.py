"""Rep cache: share of the window's user-rep lookups served from the
cache, in % (``cache_hits / (cache_hits + cache_misses)`` of the shared
``UserRepCache`` over the window)."""


def read(w):
    c = w["counters"]
    looked_up = c.get("cache_hits", 0) + c.get("cache_misses", 0)
    return 100.0 * c["cache_hits"] / looked_up if looked_up else None
