"""The engine's host time per step (ms): each step's ``serve.step`` span
less its ``.wait`` children, where the step blocks on a device result,
summed by the engine as ``host_s`` and divided by its ``steps`` (window
deltas).  An engine without ``host_s`` gives nothing."""


def read(w):
    steps = w.counters.get("steps", 0)
    if "host_s" not in w.counters or not steps:
        return None
    return 1e3 * w.counters["host_s"] / steps
