"""One span API on two sinks: the profiler's timeline and an in-memory tracer.

:func:`span` always enters a ``jax.profiler.TraceAnnotation``, so while a
profiler session runs the span is a host event on the device trace's own
clock, and idle device time can be named by what the program was doing.
When a :class:`Tracer` is active (``with tracing(t):``) the same span is
also recorded there as a complete event (``ph: "X"``) in the Chrome Trace
Event format, so ``tracer.save("run.json")`` opens in ``ui.perfetto.dev``:
spans nest by time containment per (pid, tid) track, counter tracks plot
energy-vs-step or queue depth over the run.  Each span measures its
duration once (``Span.dur_s``), so callers that keep their own counters
read it instead of timing the interval again.

With neither sink active a span costs about a microsecond: the annotation
is a no-op outside a profiler session, and no event is built.  JAX is
imported on the first span, so ``repro.obs`` imports without it.

The first span also registers one ``jax.monitoring`` listener per process:
every ``/jax/core/compile/*`` event (tracing, lowering, compiling) is
counted into the active metrics registry (``jax.compiles``, and in
``jax.compile_s`` the wall seconds it adds, nested events counted once) and
emitted as a ``jax.compile`` instant on the active tracer, so a trace shows
which span compiled.  :func:`compile_seconds` gives the process's total.

Event collection is thread-safe (the serve engine emits from its streaming
callback thread).  ``save`` writes either the Chrome JSON object
(``{"traceEvents": [...]}``, for ``.json`` paths) or JSONL (one event per
line, anything else); :func:`load_trace` and :func:`validate_events` read
and schema-check both forms (``launch/obsreport.py --validate``).
"""

from __future__ import annotations

import collections
import contextlib
import contextvars
import json
import os
import threading
import time
from typing import Any, Iterator

from repro.obs import metrics as obs_metrics

#: event fields beyond these are rejected by the validator
_EVENT_KEYS = {"name", "ph", "ts", "dur", "pid", "tid", "args", "s", "cat"}
_PHASES = {"X", "I", "C", "M"}


class Tracer:
    """Collects Chrome-trace events in memory; see module docstring."""

    def __init__(self, *, pid: int | None = None):
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._pid = os.getpid() if pid is None else pid

    # ------------------------------------------------------------ recording
    def _us(self, t: float) -> float:
        return round((t - self._t0) * 1e6, 3)

    def _emit(self, ev: dict) -> None:
        with self._lock:
            self._events.append(ev)

    def complete(self, name: str, t0: float, t1: float,
                 args: dict[str, Any]) -> None:
        """A span from ``t0`` to ``t1`` (``time.perf_counter`` seconds)."""
        ts = self._us(t0)
        self._emit({"name": name, "ph": "X", "ts": ts,
                    "dur": round(self._us(t1) - ts, 3), "pid": self._pid,
                    "tid": threading.get_ident(), "args": _jsonable(args)})

    def span(self, name: str, **args: Any) -> "Span":
        """A span recorded on this tracer (and on the profiler's timeline);
        ``sp["k"] = v`` in the body attaches a result to the event."""
        return Span(name, self, args)

    def instant(self, name: str, **args: Any) -> None:
        self._emit({"name": name, "ph": "I",
                    "ts": self._us(time.perf_counter()), "s": "t",
                    "pid": self._pid, "tid": threading.get_ident(),
                    "args": _jsonable(args)})

    def counter(self, name: str, values: dict[str, float]) -> None:
        """One sample on the counter track ``name`` (plots as a time series)."""
        self._emit({"name": name, "ph": "C",
                    "ts": self._us(time.perf_counter()),
                    "pid": self._pid, "tid": 0, "args": _jsonable(values)})

    # -------------------------------------------------------------- export
    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def to_chrome(self) -> dict:
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def save(self, path: str) -> None:
        """Chrome JSON for ``.json`` paths, JSONL otherwise."""
        if path.endswith(".json"):
            with open(path, "w") as f:
                json.dump(self.to_chrome(), f)
        else:
            with open(path, "w") as f:
                for ev in self.events():
                    f.write(json.dumps(ev) + "\n")


def _jsonable(d: dict[str, Any]) -> dict[str, Any]:
    out = {}
    for k, v in d.items():
        if hasattr(v, "item"):           # numpy scalar
            v = v.item()
        if isinstance(v, float) and not (v == v and abs(v) != float("inf")):
            out[k] = repr(v)             # inf/NaN would break strict JSON
        elif isinstance(v, (str, int, float, bool)) or v is None:
            out[k] = v
        else:
            out[k] = repr(v)
    return out


class Span:
    """One timed region on both sinks (see module docstring).  After exit
    ``dur_s`` holds its seconds; ``span["k"] = v`` attaches a result to the
    tracer's event."""

    __slots__ = ("name", "args", "dur_s", "_tracer", "_ann", "_t0")

    def __init__(self, name: str, tracer: Tracer | None,
                 args: dict[str, Any]):
        self.name = name
        self.args = args
        self.dur_s = 0.0
        self._tracer = tracer
        self._ann = None

    def __setitem__(self, key: str, value: Any) -> None:
        self.args[key] = value

    def __enter__(self) -> "Span":
        ann = _ANNOTATION or _jax_hooks()
        if ann:
            self._ann = ann(self.name, **self.args)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self.dur_s = t1 - self._t0
        if self._tracer is not None:
            self._tracer.complete(self.name, self._t0, t1, self.args)
        if self._ann is not None:
            self._ann.__exit__(*exc)


# ----------------------------------------------------------- jax hooks
#: ``jax.profiler.TraceAnnotation`` once the first span has looked for it;
#: False where JAX cannot be imported
_ANNOTATION: Any = None
_HOOK_LOCK = threading.Lock()


class _CompileWatch:
    """The process's one ``jax.monitoring`` time-span listener (JAX keeps
    listeners per process).  Compile events nest: tracing a function traces
    the jitted functions it calls, and each reports its own span.  Events
    arrive as they end, so one that holds earlier ones adds only the time
    they left uncovered, and ``seconds`` is the wall time in which JAX was
    tracing, lowering or compiling."""

    PREFIX = "/jax/core/compile/"

    def __init__(self):
        self._lock = threading.Lock()
        # the latest spans, disjoint and in order of their ends
        self._spans: collections.deque[tuple[float, float]] = \
            collections.deque(maxlen=4096)
        self.seconds = 0.0

    def __call__(self, name: str, start: float, end: float,
                 **kw: Any) -> None:
        if not name.startswith(self.PREFIX):
            return
        with self._lock:
            secs = end - start
            while self._spans and self._spans[-1][0] >= start:
                s, e = self._spans.pop()
                secs -= e - s
            self._spans.append((start, end))
            self.seconds += secs
        reg = obs_metrics.active_registry()
        reg.counter("jax.compiles").inc()
        reg.counter("jax.compile_s").inc(secs)
        instant("jax.compile", event=name[len(self.PREFIX):],
                fun=kw.get("fun_name", ""), s=end - start)


_COMPILES: _CompileWatch | None = None


def _jax_hooks():
    """Import ``TraceAnnotation`` and register the compile listener, once."""
    global _ANNOTATION, _COMPILES
    with _HOOK_LOCK:
        if _ANNOTATION is None:
            try:
                import jax.monitoring
                from jax.profiler import TraceAnnotation
            except ImportError:
                _ANNOTATION = False
            else:
                _COMPILES = _CompileWatch()
                jax.monitoring.register_event_time_span_listener(_COMPILES)
                _ANNOTATION = TraceAnnotation
    return _ANNOTATION


def compile_seconds() -> float:
    """Seconds of JAX tracing, lowering and compiling in this process since
    the listener was registered (registering it on the first call)."""
    _jax_hooks()
    return 0.0 if _COMPILES is None else _COMPILES.seconds


# --------------------------------------------------------------- scoping
_ACTIVE: contextvars.ContextVar[tuple[Tracer, ...]] = \
    contextvars.ContextVar("repro_tracer", default=())


@contextlib.contextmanager
def tracing(tracer: Tracer | None = None) -> Iterator[Tracer]:
    """Activate ``tracer`` (a fresh one when None) for a region of code."""
    tracer = Tracer() if tracer is None else tracer
    token = _ACTIVE.set(_ACTIVE.get() + (tracer,))
    try:
        yield tracer
    finally:
        _ACTIVE.reset(token)


def active_tracer() -> Tracer | None:
    """The innermost ``tracing`` scope's tracer, or None (tracing off)."""
    stack = _ACTIVE.get()
    return stack[-1] if stack else None


def span(name: str, **args: Any) -> Span:
    """A span on the profiler's timeline, recorded on the active tracer
    too when there is one."""
    stack = _ACTIVE.get()
    return Span(name, stack[-1] if stack else None, args)


def instant(name: str, **args: Any) -> None:
    t = active_tracer()
    if t is not None:
        t.instant(name, **args)


def counter(name: str, values: dict[str, float]) -> None:
    t = active_tracer()
    if t is not None:
        t.counter(name, values)


# ------------------------------------------------------- load + validation
def load_trace(path: str) -> list[dict]:
    """Events from a Chrome JSON object, a bare JSON array, or JSONL."""
    with open(path) as f:
        text = f.read()
    try:
        loaded = json.loads(text)
    except json.JSONDecodeError:
        # JSONL: every line is its own object (including single-event files,
        # which also parse above — either way the events come out the same)
        return [json.loads(line) for line in text.splitlines() if line.strip()]
    if isinstance(loaded, dict):
        events = loaded.get("traceEvents")
        if isinstance(events, list):
            return events
        if "ph" in loaded:                       # one-line JSONL file
            return [loaded]
        raise ValueError(f"{path}: JSON object without a "
                         f"'traceEvents' list")
    if isinstance(loaded, list):
        return loaded
    return [loaded]


def validate_events(events: list[dict]) -> list[str]:
    """Schema + nesting errors for a trace (empty list == valid).

    Checks each event's shape (known phase, finite non-negative ts/dur,
    required ids) and that "X" spans on each (pid, tid) track nest properly
    by time containment — a child must end no later than its parent, which
    is exactly what Perfetto assumes when it stacks them.
    """
    errors: list[str] = []
    spans: dict[tuple, list[tuple[float, float, str]]] = {}
    for i, ev in enumerate(events):
        if not isinstance(ev, dict):
            errors.append(f"event {i}: not an object: {ev!r}")
            continue
        extra = set(ev) - _EVENT_KEYS
        if extra:
            errors.append(f"event {i}: unknown fields {sorted(extra)}")
        ph = ev.get("ph")
        if ph not in _PHASES:
            errors.append(f"event {i}: bad phase {ph!r}")
            continue
        if not isinstance(ev.get("name"), str) or not ev.get("name"):
            errors.append(f"event {i}: missing name")
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            errors.append(f"event {i}: bad ts {ts!r}")
            continue
        if "pid" not in ev or "tid" not in ev:
            errors.append(f"event {i}: missing pid/tid")
            continue
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errors.append(f"event {i}: X event with bad dur {dur!r}")
                continue
            spans.setdefault((ev["pid"], ev["tid"]), []).append(
                (float(ts), float(dur), ev["name"]))
        if ph == "C" and not isinstance(ev.get("args"), dict):
            errors.append(f"event {i}: C event without args values")
    eps = 0.01                                   # µs; ts is rounded to 1e-3
    for track, evs in spans.items():
        # outermost-first at equal start, then check stack containment
        evs.sort(key=lambda e: (e[0], -e[1]))
        stack: list[tuple[float, float, str]] = []
        for ts, dur, name in evs:
            while stack and stack[-1][0] + stack[-1][1] <= ts + eps:
                stack.pop()
            if stack:
                pts, pdur, pname = stack[-1]
                if ts + dur > pts + pdur + eps:
                    errors.append(
                        f"track {track}: span {name!r} [{ts}, {ts + dur}] "
                        f"overlaps parent {pname!r} [{pts}, {pts + pdur}] "
                        f"without nesting")
            stack.append((ts, dur, name))
    return errors


def validate_trace(path: str) -> list[str]:
    try:
        events = load_trace(path)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        return [f"{path}: unreadable trace ({e})"]
    return validate_events(events)
