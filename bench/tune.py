"""Tuning cells: the paper's own loop, SIP's stochastic search over a
Pallas kernel's schedule, at a shape the served model dispatches.

Set-up makes the inputs from the seed on the device and runs the search
(``SipKernel.tune`` with the mix's energy, warm-started round after round
from the previous round's best) for the mix's fixed number of rounds; every
candidate the search tests compiles there, and the winner too.  The window then calls the
tuned kernel, resolved from the schedule store the search filled, back to
back: ``tuned_kernel_us`` is the window over the calls, on the host clock,
every batch of calls ending in ``block_until_ready``.  The last call's
output is checked against plain attention in ``bench/reference``.
"""

from __future__ import annotations

import dataclasses
import time

from bench import flops
from bench.common import Check, Outcome, Run, annotate, log, peak_bytes

BATCH = 32              # calls between host syncs in the window
TRACE_S = 1.0


@dataclasses.dataclass
class TuneWindow:
    """What the per-layer readers of a tuning cell read."""

    flops_per_call: float
    bytes_per_call: float
    us_per_call: float              # host clock, whole window
    evals: int
    search_s: float
    peaks: object = None
    traced_calls: int = 0
    traced: object = None           # trace_reduce.Reduced


def _inputs(seed: int, shape, dtype):
    """q, k, v with standard normal entries, made on the device."""
    import jax
    from bench import weights as W
    b, hq, hkv, s, d = shape

    @jax.jit
    def make(key):
        ks = jax.random.split(key, 3)
        return tuple(jax.random.normal(kk, (b, h, s, d)).astype(dtype)
                     for kk, h in zip(ks, (hq, hkv, hkv)))
    return make(W.seed_key(seed))


def drive(run: Run) -> Outcome:
    import jax
    import jax.numpy as jnp
    from bench import peaks as P
    from bench.reference import attention as ref
    from repro.core.cache import ScheduleCache
    from repro.core.jit import TuneConfig
    from repro.core.registry import registry
    from repro.kernels.flash_attention import ops as fa_ops

    mix, c = run.mix, run.config
    if mix["kernel"] != "flash_attention_causal":
        raise ValueError(f"no tuning job for kernel {mix['kernel']!r}")
    shape = (mix["batch"], c["num_attention_heads"], c["num_key_value_heads"],
             mix["seq"], c["head_dim"])
    dtype = jnp.dtype(c["torch_dtype"])
    with annotate("bench.inputs"):
        q, k, v = _inputs(run.seed, shape, dtype)
        jax.block_until_ready(v)
    store = ScheduleCache()
    kern = registry.get(fa_ops.ensure_registered(causal=True), cache=store)
    best, evals = None, 0
    t0 = time.perf_counter()
    for r in range(mix["rounds"]):
        cfg = TuneConfig(energy=mix["energy"], rounds=1, cooling=mix["cooling"],
                         knob_prob=mix["knob_prob"],
                         step_samples=mix["step_samples"],
                         final_samples=mix["final_samples"],
                         seed=mix["search_seed"] + r)
        with annotate("bench.tune_round"):
            res, = kern.tune([q, k, v], cfg, x0=best)
        best, evals = res.best, evals + res.evals
    search_s = time.perf_counter() - t0
    sig = kern.sig_str(kern.static_of(q, k, v))
    winner = store.best(kern.name, sig)
    log("search", rounds=mix["rounds"], evals=evals, search_s=search_s,
        default_us=res.initial_raw * 1e6, winner_energy_us=res.best_raw * 1e6,
        winner=winner.to_json() if winner is not None else None)
    with annotate("bench.warmup"):
        out = kern(q, k, v)
        jax.block_until_ready(out)
    setup_s = time.perf_counter() - run.t_start
    calls, traced_calls = 0, 0
    tracing = False
    compiles0 = run.clock.events
    t0 = time.perf_counter()
    end = t0 + run.seconds
    trace_from = t0 + 0.4 * run.seconds
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        if run.trace and not tracing and traced_calls == 0 and \
                now >= trace_from:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(run.trace_dir),
                                     profiler_options=opts)
            span = annotate("bench.traced")
            span.__enter__()
            tracing, trace_to = True, now + min(TRACE_S, 0.3 * run.seconds)
        with annotate("bench.calls"):
            for _ in range(BATCH):
                out = kern(q, k, v)
            jax.block_until_ready(out)
        calls += BATCH
        if tracing:
            traced_calls += BATCH
            if time.perf_counter() >= trace_to:
                span.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = False
    elapsed = time.perf_counter() - t0
    if tracing:
        span.__exit__(None, None, None)
        jax.profiler.stop_trace()
    b, hq, hkv, s, d = shape
    win = TuneWindow(
        flops_per_call=flops.causal_attention_flops(b, hq, s, d),
        bytes_per_call=flops.attention_bytes(b, hq, hkv, s, d,
                                             dtype.itemsize),
        us_per_call=elapsed / calls * 1e6, evals=evals, search_s=search_s,
        peaks=P.for_kind(run.devices[0].device_kind) if run.trace else None,
        traced_calls=traced_calls)
    log("window", calls=calls, elapsed_s=elapsed,
        compiles_in_window=run.clock.events - compiles0,
        us_per_call=win.us_per_call)
    mem = peak_bytes(run.devices)
    lim = c["limits"]["attention_rel_err"]
    with annotate("bench.reference"):
        want = ref.causal(q, k, v)
        checks = [Check("attention_rel_err", ref.rel_err(out, want), lim)]
        if run.control:
            checks.append(Check("control_rel_err", ref.rel_err(
                ref.causal(q, k, v, quant="fp8"), want), lim))
    return Outcome(
        checks=checks,
        attempted=calls, failed=0,
        end_to_end={"setup_s": setup_s, "tuned_kernel_us": win.us_per_call},
        window=win, memory_peak_bytes=mem)
