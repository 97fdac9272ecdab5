"""Device: share of the traced stretch in which no operation ran on the
chip, in % (1 - union of the XLA op intervals / traced seconds)."""


def read(w):
    t = w["trace"]
    if not t or not t.get("devices"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
