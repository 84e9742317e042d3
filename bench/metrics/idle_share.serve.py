"""Share of the traced window in which no operation ran on the device (%)."""

from bench.metrics import _serve


def read(w):
    return _serve.idle_share(w)
