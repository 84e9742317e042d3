"""Prefill chunks' share of the chips' bf16 peak (%): the FLOPs their real
tokens need at their live context (``bench/flops.py``), over the device time
of the chunk programs in the traced window."""

from bench.metrics import _serve


def read(w):
    return _serve.prefill_mfu(w)
