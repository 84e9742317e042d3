"""Name a traced window's idle device time by the program's own spans.

``bench/trace_reduce.py`` names each idle gap on chip 0 by the innermost
``bench.*`` host span over its midpoint.  This names each gap the same way
by the innermost host span of either kind: the harness's ``bench.*`` spans
or the program's ``serve.*``, ``tune.*`` and ``jax.*`` spans
(``repro.obs.trace.span``).  Program-run and operation attribution, and
every metric read from them, stay with ``trace_reduce``.

    python bench/idle_spans.py TRACE.xplane.pb[.gz]   # prints the gaps
"""

from __future__ import annotations

import collections
import gzip
import json
import sys

from bench import trace_reduce as T

PROGRAM = ("serve.", "tune.", "jax.")
HOST = ("bench.",) + PROGRAM


def _profile(path: str):
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


def idle_by_span(path: str) -> dict[str, float]:
    """Idle seconds on chip 0 in the traced window, by the innermost host
    span over each gap's midpoint (``host:other`` where there is none)."""
    host, chip0 = [], None
    for plane in _profile(path).planes:
        if plane.name.startswith("/host:"):
            host += [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                     for line in plane.lines for ev in line.events
                     if ev.name.startswith(HOST)]
        elif plane.name == "/device:TPU:0":
            chip0 = plane
    win = [(a, b) for a, b, n in host if n == T.WINDOW]
    if len(win) != 1 or chip0 is None:
        raise ValueError(f"expected one {T.WINDOW!r} host span and a TPU "
                         f"plane, found {len(win)} and {chip0 is not None}")
    (w0, w1), = win
    host = [h for h in host if h[2] != T.WINDOW]
    ops, = [ln.events for ln in chip0.lines if ln.name == T.OPS_LINE]
    busy = T._union([se for ev in ops for se in [T._clip(ev, w0, w1)]
                     if se[1] > se[0]])
    out: collections.Counter = collections.Counter()
    for s, e in T._subtract([[w0, w1]], busy):
        out[T._innermost(host, (s + e) / 2) or "host:other"] += (e - s) / 1e9
    return dict(out)


def program_share(idle: dict[str, float]) -> float:
    """Share of the idle seconds named by a program span (0 to 1)."""
    total = sum(idle.values())
    named = sum(v for k, v in idle.items() if k.startswith(PROGRAM))
    return named / total if total else 0.0


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    idle = idle_by_span(argv[0])
    print(json.dumps({"idle_by_span": dict(sorted(
        idle.items(), key=lambda kv: -kv[1])),
        "program_share": program_share(idle)}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
