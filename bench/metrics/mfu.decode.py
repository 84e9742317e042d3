"""Decode steps' share of the chips' bf16 peak (%): the FLOPs of the tokens
the occupied slots decode, at their live context (``bench/flops.py``), over
the device time of the decode programs in the traced window.  A padded page
table counts for nothing."""

from bench.metrics import _serve


def read(w):
    t = _serve.module_seconds(w, "decode")
    if not t or not w.traced_decode_flops:
        return None
    return 100.0 * w.traced_decode_flops / (t * w.chips * w.peaks.bf16_flops)
