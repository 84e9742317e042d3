"""Candidate schedules the SIP search scored per second of search (every
one compiled, tested and timed)."""


def read(w):
    return w.evals / w.search_s if w.search_s else None
