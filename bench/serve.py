"""Serving cells: a language model served by the program's
``ContinuousEngine`` under one request mix.

The harness knows the model's family only through the configuration's
model module (``model_module``), so a family is added by files alone.
Set-up makes the weights from the seed on the device, builds the engine
with the configuration's settings, and runs two requests through it so that
every program the mix dispatches (prefill chunk, decode step, page-table
splices, sampling) is compiled before the window opens.  The window drives
``submit`` and ``step`` from this one thread; every token is timed where the
engine hands it to the host (``on_token``).  After the window the served
tokens of a sample of finished requests are checked against the model
module's float32 reference.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench import traffic
from bench import weights as W
from bench.common import (Check, Outcome, Run, annotate, load_file, log,
                          peak_bytes)

TRACE_AT = 0.4          # the traced steps start at this share of the window
TRACE_S = 4.0           # and last this long


def model_module(root, config: dict):
    """The configuration's model module, the file its ``"model"`` names: the
    family's whole contract with the harness.  It provides

    - ``Spec.from_config(config)``: the published sizes, as the file states
      them; ``spec.vocab`` bounds the token ids the traffic draws;
    - ``check_program(cfg, spec)``: raises, naming both sides, unless the
      program's ``ModelConfig`` serves what the file states;
    - ``program_params(key, spec, dtype)`` and ``make(seed, spec, dtype)``:
      the weights from the seed, in the program's layout;
    - ``logits(seed, spec, seqs, reads, *, pad_to, quant=None)``: the plain
      float32 reference; ``quant="fp8"`` gives its control;
    - ``token_flops(spec, ctx)`` and ``chunk_flops(spec, start, n, last)``:
      the FLOPs a token decoded at context ``ctx`` and a prefill chunk
      need;
    - ``kv_read_bytes(spec, ctx, page_size)``: the cache bytes that
      attention for one row at context ``ctx`` must read, summed over
      layers, in whole pages."""
    return load_file(root, config["model"])


def program_config(model, config: dict):
    """The program's model configuration for a configuration file, held to
    the sizes the file states by its model module."""
    from repro import configs
    prog = config["program"]
    cfg = dataclasses.replace(configs.get(prog["arch"]), **prog["overrides"])
    spec = model.Spec.from_config(config)
    model.check_program(cfg, spec)
    return cfg, spec


class Tokens:
    """Host arrival time of every token, and the work of each step: the
    FLOPs of its decoded tokens and prefill chunk, and the cache bytes
    their attention reads (the model module's counts)."""

    def __init__(self, model, spec, page_size: int):
        self.model, self.spec, self.page_size = model, spec, page_size
        self.times: dict[int, list[float]] = {}
        self.step = 0
        self.decode_flops: dict[int, float] = {}     # step -> FLOPs
        self.chunk_flops: dict[int, float] = {}
        self.kv_bytes: dict[int, float] = {}         # step -> bytes

    def _add(self, counts: dict, v) -> None:
        counts[self.step] = counts.get(self.step, 0) + v

    def on_token(self, req, tok) -> None:
        self.times.setdefault(req.uid, []).append(time.perf_counter())
        k = len(req.tokens)
        if k > 1:       # every token after the first comes from a decode
            ctx = len(req.prompt) + k - 1
            self._add(self.decode_flops,
                      self.model.token_flops(self.spec, ctx))
            self._add(self.kv_bytes, self.model.kv_read_bytes(
                self.spec, ctx, self.page_size))

    def watch(self, engine, chunk: int) -> bool:
        """Name the engine's prefill-chunk and decode dispatches on the
        profiler's clock (``bench.chunk``, ``bench.decode``) and record
        each chunk's work as it runs.  Reads the engine's chunk queue;
        returns False, and records nothing, where the engine has none."""
        chunk_step = getattr(engine, "_chunk_step", None)
        decode = getattr(engine, "_decode_paged", None)
        if chunk_step is None or decode is None or \
                not hasattr(engine, "_chunk_tasks"):
            return False

        def traced_chunk(finished):
            t = engine._chunk_tasks[0]
            n = min(chunk, len(t.req.prompt) - t.pos)
            self._add(self.chunk_flops, self.model.chunk_flops(
                self.spec, t.pos, n, t.pos + n >= len(t.req.prompt)))
            self._add(self.kv_bytes, self.model.kv_read_bytes(
                self.spec, t.pos + n, self.page_size))
            with annotate("bench.chunk"):
                return chunk_step(finished)

        def traced_decode(finished):
            with annotate("bench.decode"):
                return decode(finished)

        engine._chunk_step = traced_chunk
        engine._decode_paged = traced_decode
        return True


@dataclasses.dataclass
class ServeWindow:
    """What the per-layer readers of a serving cell read."""

    spec: object                    # the model module's Spec
    chips: int
    peaks: object
    seconds: float
    counters: dict                  # engine counters, window delta
    decode_flops: float             # whole window
    chunk_flops: float
    traced: object = None           # trace_reduce.Reduced
    traced_decode_flops: float = 0.0
    traced_chunk_flops: float = 0.0
    traced_kv_bytes: float = 0.0    # read by the traced steps' attention
    chunks_seen: bool = True
    requests: list = dataclasses.field(default_factory=list)  # (due, first)
    profiler_s: list = dataclasses.field(default_factory=list)  # (from, to)


def _stats(engine) -> dict:
    return dict(engine.stats)


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in b}


def setup(run: Run):
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    from repro.models import modules as nn
    from repro.serve.engine import ContinuousEngine, ServeConfig
    if run.cell["chips"] != 1:
        raise ValueError("serving cells run on one chip")
    t0 = time.perf_counter()
    model = model_module(run.root, run.config)
    cfg, spec = program_config(model, run.config)
    dtype = jnp.dtype(cfg.param_dtype)
    key = jax.random.PRNGKey(0)
    W.check_layout(jax.eval_shape(
        lambda k: model.program_params(k, spec, dtype), key),
        nn.unwrap(M.init_lm_shapes(key, cfg)))
    with annotate("bench.weights"):
        params = model.make(run.seed, spec, dtype)
        jax.block_until_ready(params)
    t_weights = time.perf_counter()
    e = run.config["engine"]
    scfg = ServeConfig(max_len=e["max_len"], capacity=e["capacity"],
                       paged=True, page_size=e["page_size"],
                       num_pages=e["num_pages"],
                       prefill_chunk=e["prefill_chunk"],
                       prefix_cache=e["prefix_cache"])
    tokens = Tokens(model, spec, e["page_size"])
    engine = ContinuousEngine(params, cfg, scfg, on_token=tokens.on_token)
    del params
    seen = tokens.watch(engine, e["prefill_chunk"])
    t_engine = time.perf_counter()
    with annotate("bench.warmup"):
        rng = np.random.default_rng(run.seed ^ 0x5EED)
        first = rng.integers(0, spec.vocab, e["prefill_chunk"] + 17,
                             dtype=np.int32)
        # the second shares the first's full pages: the prefix-hit path
        second = np.concatenate([first[:2 * e["page_size"]],
                                 rng.integers(0, spec.vocab, 40,
                                              dtype=np.int32)])
        engine.submit(first, 3)
        engine.step()
        engine.submit(second, 3)
        engine.run(max_steps=1000)
    log("setup", imports_s=t0 - run.t_start, weights_s=t_weights - t0,
        engine_s=t_engine - t_weights,
        warmup_s=time.perf_counter() - t_engine,
        compiles=run.clock.events, compile_s=run.clock.seconds)
    return engine, model, spec, tokens, seen


def _sample(finished, rng, k: int):
    """The longest finished request and ``k - 1`` others drawn from the
    seed."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.prompt) + len(r.tokens),
                                           r.uid))
    rest = [r for r in finished if r is not longest]
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def served_gaps(ref: np.ndarray, served) -> np.ndarray:
    """How far below the reference's best logit each served token's logit
    lies, at each position."""
    served = np.asarray(served)
    return ref.max(-1) - ref[np.arange(len(served)), served]


def check_tokens(run: Run, model, spec, sample) -> list[Check]:
    seqs = [np.concatenate([r.prompt, np.asarray(r.tokens[:-1], np.int32)])
            for r in sample]
    reads = [np.arange(len(r.prompt) - 1, len(r.prompt) - 1 + len(r.tokens))
             for r in sample]
    pad = run.config["engine"]["max_len"]
    ref = model.logits(run.seed, spec, seqs, reads, pad_to=pad)
    widest = max(float(served_gaps(x, r.tokens).max())
                 for x, r in zip(ref, sample))
    lim = run.config["limits"]["served_logit_gap"]
    checks = [Check("served_logit_gap", widest, lim)]
    if run.control:
        # the token the float8 forward puts first, at each same position
        low = model.logits(run.seed, spec, seqs, reads, pad_to=pad,
                              quant="fp8")
        checks.append(Check("control_logit_gap", max(
            float(served_gaps(x, y.argmax(-1)).max())
            for x, y in zip(ref, low)), lim))
    return checks


def _percentile_ms(xs, q: float) -> float:
    return float(np.percentile(np.asarray(xs), q) * 1e3)


class Window:
    """The measured window's loop: offers load, steps the engine, and
    traces the middle of the window in a ``--trace 1`` run."""

    def __init__(self, run: Run, engine, tokens: Tokens):
        self.run, self.engine, self.tokens = run, engine, tokens
        self.t0 = self.end = 0.0
        self.trace_span = None
        self.traced_steps: tuple[int, int] | None = None
        # host seconds spent in the profiler's start and stop calls
        self.profiler_s: list[tuple[float, float]] = []

    def open(self) -> None:
        self.t0 = time.perf_counter()
        self.end = self.t0 + self.run.seconds
        self.start_stats = _stats(self.engine)
        self.start_compiles = self.run.clock.events
        self._trace_from = self.t0 + TRACE_AT * self.run.seconds
        self._trace_to = None

    def step(self) -> None:
        self._maybe_trace()
        with annotate("bench.step"):
            self.engine.step()
        self.tokens.step += 1

    def _maybe_trace(self) -> None:
        if not self.run.trace:
            return
        import jax
        now = time.perf_counter()
        if self._trace_to is None and now >= self._trace_from:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(self.run.trace_dir),
                                     profiler_options=opts)
            self.profiler_s.append((now, time.perf_counter()))
            self.trace_span = annotate("bench.traced")
            self.trace_span.__enter__()
            self._trace_to = now + min(TRACE_S, 0.5 * self.run.seconds)
            self.traced_steps = (self.tokens.step, None)
        elif self.trace_span is not None and now >= self._trace_to:
            self._stop_trace()

    def _stop_trace(self) -> None:
        import jax
        self.trace_span.__exit__(None, None, None)
        self.trace_span = None
        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.profiler_s.append((t, time.perf_counter()))
        self.traced_steps = (self.traced_steps[0], self.tokens.step)

    def wait_until(self, t: float) -> None:
        with annotate("bench.idle"):
            time.sleep(max(0.0, min(t, self.end) - time.perf_counter()))

    def close(self) -> dict:
        if self.trace_span is not None:
            self._stop_trace()
        self.compiles = self.run.clock.events - self.start_compiles
        return _delta(self.start_stats, _stats(self.engine))


def _drive_poisson(w: Window, plan, handles):
    i, n = 0, len(plan)
    eng = w.engine
    while time.perf_counter() < w.end:
        now = time.perf_counter()
        while i < n and w.t0 + plan[i].due <= now:
            with annotate("bench.submit"):
                handles[i] = eng.submit(plan[i].prompt, plan[i].max_new)
            i += 1
        if eng.pool.idle:
            w.wait_until(w.t0 + plan[i].due if i < n else w.end)
            continue
        w.step()


class Sessions:
    """Multi-turn sessions driven across the ramp and the window: each
    session's next turn is due a think time after its previous answer
    reached the host; ``drive`` appends (due, request) pairs."""

    def __init__(self, systems, sessions, t0: float, max_len: int):
        self.sessions, self.max_len = sessions, max_len
        self.due = [t0 + s.start for s in sessions]
        self.turn = [0] * len(sessions)
        self.history = [systems[s.system] for s in sessions]
        self.live: dict[int, object] = {}

    def _waiting(self, j: int) -> bool:
        return j not in self.live and \
            self.turn[j] < len(self.sessions[j].users)

    def drive(self, w: Window, handles) -> None:
        eng = w.engine
        while time.perf_counter() < w.end:
            now = time.perf_counter()
            for j, s in enumerate(self.sessions):
                if not self._waiting(j) or self.due[j] > now:
                    continue
                k = self.turn[j]
                prompt = np.concatenate([self.history[j], s.users[k]])
                if len(prompt) + s.outputs[k] > self.max_len:
                    self.turn[j] = len(s.users)     # the session ends here
                    continue
                with annotate("bench.submit"):
                    req = eng.submit(prompt, s.outputs[k])
                handles.append((self.due[j], req))
                self.live[j] = req
            if eng.pool.idle:
                w.wait_until(min((self.due[j] for j in
                                  range(len(self.sessions))
                                  if self._waiting(j)), default=w.end))
                continue
            w.step()
            for j in [j for j, r in self.live.items() if r.done]:
                r = self.live.pop(j)
                self.history[j] = np.concatenate(
                    [r.prompt, np.asarray(r.tokens, np.int32)])
                self.due[j] = r.finished_at + \
                    self.sessions[j].thinks[self.turn[j]]
                self.turn[j] += 1


def drive(run: Run) -> Outcome:
    import jax
    from bench import peaks as P
    mix, e = run.mix, run.config["engine"]
    engine, model, spec, tokens, seen = setup(run)
    rng = np.random.default_rng(run.seed)
    with annotate("bench.generator"):
        kind = mix["kind"]
        if kind == "poisson":
            plan = traffic.poisson(mix, run.seconds, spec.vocab, rng)
            handles = [None] * len(plan)
        elif kind == "sessions":
            # one schedule over the ramp and the window: sessions that
            # start in the ramp take their later turns in the window
            systems, plan = traffic.sessions(
                mix, mix.get("ramp_s", 0) + run.seconds, spec.vocab, rng)
            sessions = Sessions(systems, plan, time.perf_counter(),
                                e["max_len"])
            handles = []
        else:
            raise ValueError(f"serving cells take poisson or sessions "
                             f"mixes, not {kind!r}")
    ramped = []
    if mix.get("ramp_s"):
        # the same load runs before the window opens, so that the window
        # sees the engine in its steady state, not filling from empty
        rr = dataclasses.replace(run, trace=False, seconds=mix["ramp_s"])
        ramp = Window(rr, engine, tokens)
        with annotate("bench.ramp"):
            ramp.open()
            if kind == "poisson":
                pre = traffic.poisson(mix, rr.seconds, spec.vocab, rng,
                                      part="ramp")
                ramped = [None] * len(pre)
                _drive_poisson(ramp, pre, ramped)
            else:
                sessions.drive(ramp, ramped)
                ramped = [h for _, h in ramped]
            ramp.close()
        log("ramp", seconds=time.perf_counter() - ramp.t0,
            compiles=ramp.compiles)
    # no cyclic collection pauses the loop inside the window: set-up's
    # objects are frozen out of the collector, which runs again after it
    gc.collect()
    gc.freeze()
    gc.disable()
    setup_s = time.perf_counter() - run.t_start
    w = Window(run, engine, tokens)
    step0 = tokens.step
    w.open()
    with annotate("bench.window"):
        if kind == "poisson":
            _drive_poisson(w, plan, handles)
        else:
            sessions.drive(w, handles)
    counters = w.close()
    gc.enable()
    gc.unfreeze()
    t0, end = w.t0, w.end
    if kind == "poisson":
        due = [(t0 + p.due, h) for p, h in zip(plan, handles)
               if t0 + p.due < end]
    else:
        due = [(d, h) for d, h in handles if t0 <= d < end]
    ttft, late, requests = [], [], []
    for d, h in due:
        times = [] if h is None else tokens.times.get(h.uid, [])
        first = times[0] if times and times[0] < end else end
        ttft.append(first - d)
        requests.append((d, first))
        if h is not None:
            late.append(h.submitted_at - d)
    # every request's tokens in the window, those of the ramp's too
    itl, window_tokens = [], 0
    for ts in tokens.times.values():
        ts = [t for t in ts if t0 <= t < end]
        window_tokens += len(ts)
        itl += list(np.diff(ts))
    log("window", requests_due=len(due), compiles_in_window=w.compiles,
        prefill_compiles_in_window=counters.get("prefill_compiles", 0),
        generator_late_max_ms=max(late, default=0.0) * 1e3,
        generator_late_p95_ms=float(np.percentile(late, 95)) * 1e3
        if late else 0.0,
        tokens_per_s=window_tokens / run.seconds,
        ttft_ms_top=sorted((x * 1e3 for x in ttft), reverse=True)[:8],
        ttft_p50_ms=_percentile_ms(ttft, 50) if ttft else 0.0,
        output_tokens=window_tokens, steps=tokens.step - step0,
        counters=counters)
    e2e = {"setup_s": setup_s, "tokens_per_s": window_tokens / run.seconds}
    if ttft:
        e2e["ttft_p95_ms"] = _percentile_ms(ttft, 95)
    if itl:
        e2e["itl_p95_ms"] = _percentile_ms(itl, 95)
    steps = range(step0, tokens.step)
    win = ServeWindow(
        spec=spec, chips=run.cell["chips"],
        peaks=P.for_kind(run.devices[0].device_kind) if run.trace else None,
        seconds=run.seconds, counters=counters,
        decode_flops=sum(tokens.decode_flops.get(s, 0.0) for s in steps),
        chunk_flops=sum(tokens.chunk_flops.get(s, 0.0) for s in steps),
        chunks_seen=seen, requests=requests, profiler_s=w.profiler_s)
    if w.traced_steps is not None:
        a, b = w.traced_steps
        win.traced_decode_flops = sum(tokens.decode_flops.get(s, 0.0)
                                      for s in range(a, b))
        win.traced_chunk_flops = sum(tokens.chunk_flops.get(s, 0.0)
                                     for s in range(a, b))
        win.traced_kv_bytes = sum(tokens.kv_bytes.get(s, 0.0)
                                  for s in range(a, b))
    # every request that finished in the window, the ramp's too
    finished = [h for h in ramped + [h for _, h in due]
                if h is not None and h.done and t0 <= h.finished_at < end]
    sample = _sample(finished, rng, run.config["limits"]["sample_requests"])
    mem = peak_bytes(run.devices)
    del engine, w
    gc.collect()
    jax.clear_caches()
    if not sample:
        checks = [Check("finished_requests", 0.0, -1.0)]
    else:
        with annotate("bench.reference"):
            checks = check_tokens(run, model, spec, sample)
    return Outcome(checks=checks, attempted=len(due), failed=0,
                   end_to_end=e2e, window=win, memory_peak_bytes=mem)
