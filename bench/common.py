"""Pieces every kind of cell shares: compile accounting, device facts,
annotations on the profiler's clock, and the run context."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys
from typing import Any


class CompileClock:
    """Seconds and events of JAX tracing, lowering and compiling, from its
    own monitoring events."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name: str, secs: float, **_) -> None:
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
            self.events += 1


def peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices``."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def device_info(devices) -> dict:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def annotate(name: str, **kw):
    """A host span in the profiler's own trace (a no-op when not tracing)."""
    import jax
    return jax.profiler.TraceAnnotation(name, **kw)


def log(tag: str, **fields) -> None:
    print(f"[bench] {tag} {json.dumps(fields, default=float)}", flush=True)


@dataclasses.dataclass
class Check:
    """One number compared for ``correct``: it must not exceed ``limit``."""

    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a cell's run hands back to the harness."""

    checks: list[Check]
    attempted: int
    failed: int
    end_to_end: dict[str, float]
    window: Any = None            # what the per-layer readers read
    memory_peak_bytes: int = 0


@dataclasses.dataclass
class Run:
    """One run of one cell, as the harness sets it up."""

    root: pathlib.Path
    cell: dict
    config: dict
    mix: dict
    seed: int
    seconds: float
    trace: bool
    devices: list
    clock: CompileClock
    t_start: float                  # perf_counter at process start
    trace_dir: pathlib.Path
    control: bool = False           # also read the float8 control


def load_file(root: pathlib.Path, rel: str):
    """The Python file at ``rel`` in the checkout, imported by its path
    once per process under its dotted path (``bench/metrics/x.py`` is
    ``bench.metrics.x``), so that an import by name finds the same module.
    Metric readers and configurations' model modules are found so: a new
    one is a new file, which no existing file names."""
    name = rel.removesuffix(".py").replace("/", ".")
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, root / rel)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def eprint(*a) -> None:
    print(*a, file=sys.stderr, flush=True)
