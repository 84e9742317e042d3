"""Mean number of requests waiting for a slot, over the engine steps of the
window (the engine's queue-depth sum over its step count)."""


def read(w):
    steps = w.counters.get("steps", 0)
    if not steps:
        return None
    return w.counters["queue_depth_sum"] / steps
