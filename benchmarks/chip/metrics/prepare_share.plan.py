"""Share of the window's host time spent in the method's prepare (trace,
graph build, pack, encode) against prepare plus plan (K-sweep, plan build),
from the benchmark's spans around the two calls."""


def read(view):
    s = view["spans"]
    prep, plan = s.get("prepare", 0.0), s.get("plan", 0.0)
    if prep + plan <= 0:
        return None
    return 100.0 * prep / (prep + plan)
