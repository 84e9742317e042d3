"""``mfu.prefill`` in the sessions cell, which reports no TTFT tail end to
end: there the chunk steps, which also decode, move the ITL tail."""

from bench.metrics import _serve


def read(w):
    return _serve.prefill_mfu(w)
