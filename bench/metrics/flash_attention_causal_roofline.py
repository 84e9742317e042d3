"""The tuned causal flash-attention kernel's share of its roofline (%): the
larger of its FLOPs over the bf16 peak and its least bytes over HBM
bandwidth (``bench/flops.py``), over its device time per call in the trace.
Masked-out blocks count for nothing."""


def read(w):
    t = w.traced
    if t is None or not w.traced_calls:
        return None
    secs = t.kernel_s.get("bench.calls", 0.0) / w.traced_calls
    if not secs:
        return None
    least = max(w.flops_per_call / w.peaks.bf16_flops,
                w.bytes_per_call / w.peaks.hbm_bytes_per_s)
    return 100.0 * least / secs
