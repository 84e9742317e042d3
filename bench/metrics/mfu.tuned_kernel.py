"""The tuning cell's whole window as a share of the chip's bf16 peak (%):
the causal attention FLOPs of every call (``bench/flops.py``) over the
window's host-clock seconds per call, dispatch included."""


def read(w):
    return 100.0 * w.flops_per_call / (w.us_per_call * 1e-6
                                       * w.peaks.bf16_flops)
