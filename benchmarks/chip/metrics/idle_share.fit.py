"""Device idle share of the traced window: 1 - busy union / window."""


def read(view):
    t = view["trace"]
    if t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
