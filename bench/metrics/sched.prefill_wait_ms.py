"""Mean time from a request's submission to its first prefill dispatch
(ms), over the requests that reached their first prefill or prefill chunk
in the window: the engine's ``prefill_wait_s`` over its ``prefill_starts``
(window deltas).  An engine without those counters gives nothing."""


def read(w):
    n = w.counters.get("prefill_starts", 0)
    if not n:
        return None
    return 1e3 * w.counters["prefill_wait_s"] / n
