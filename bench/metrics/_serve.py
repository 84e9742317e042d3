"""Trace lookups shared by the serving cells' readers.  The harness names
the engine's decode and prefill-chunk dispatches ``bench.decode`` and
``bench.chunk`` on the profiler's clock; the trace reduction gives each
program run on chip 0 to the host span that dispatched it."""


def module_seconds(w, part: str) -> float:
    """Device seconds on chip 0 of the programs dispatched in
    ``bench.<part>``."""
    t = w.traced
    return 0.0 if t is None else t.span_s.get(f"bench.{part}", 0.0)


def kernel_seconds(w, part: str) -> float:
    """Device seconds on chip 0 of compiled kernels in those programs."""
    t = w.traced
    return 0.0 if t is None else t.kernel_s.get(f"bench.{part}", 0.0)


def idle_share(w):
    t = w.traced
    if t is None or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def prefill_mfu(w):
    """Prefill chunks' share of the chips' bf16 peak (%) in the traced
    window."""
    t = module_seconds(w, "chunk")
    if not t or not w.traced_chunk_flops:
        return None
    return 100.0 * w.traced_chunk_flops / (t * w.chips * w.peaks.bf16_flops)
