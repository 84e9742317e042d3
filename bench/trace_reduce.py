"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time,
time per device operation and per program, and idle gaps named by what the
host was doing.

The traced window is the host span ``bench.traced`` that the harness wraps
around the traced steps.  Device activity is read from each TPU plane's
``XLA Ops`` line; an operation's time is clipped to the window.  Busy time
is the union of the operations' intervals on a chip, averaged over the
chips used.  Each program run on chip 0 (``XLA Modules``) belongs to the
innermost ``bench.*`` host span around its start, the call that dispatched
it, and each operation to the program run around it.  An idle gap on chip 0
is named by the innermost ``bench.*`` host span over its midpoint
(``host:other`` when there is none).

    python bench/trace_reduce.py TRACE.xplane.pb   # prints the reduction
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import gzip
import json
import os
import sys

WINDOW = "bench.traced"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NESTING = ("while", "conditional", "call")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over chips
    chips: int
    op_s: dict[str, float]              # "span:op" -> seconds, chip 0
    span_s: dict[str, float]            # host span -> program seconds, chip 0
    span_calls: dict[str, int]          # host span -> program runs, chip 0
    kernel_s: dict[str, float]          # host span -> compiled-kernel seconds
    idle_by_host: dict[str, float]      # chip 0

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> int:
    return sum(e - s for s, e in intervals)


def _subtract(a, b):
    """Parts of the (sorted, disjoint) intervals ``a`` outside ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def op_name(text: str) -> str:
    """An HLO instruction's short name: ``%fusion.12 = ...`` -> ``fusion``;
    a compiled Pallas kernel is ``tpu_custom_call``."""
    if 'custom_call_target="tpu_custom_call"' in text:
        return "tpu_custom_call"
    head = text.split(" = ", 1)[0].lstrip("%")
    return head.rstrip("0123456789").rstrip(".") or head


def _innermost(spans, t):
    """Name of the shortest host span holding time ``t``, or None."""
    best = None
    for a, b, n in spans:
        if a <= t < b and (best is None or b - a < best[0]):
            best = (b - a, n)
    return None if best is None else best[1]


def _clip(ev, w0, w1):
    s = ev.start_ns
    return max(s, w0), min(s + ev.duration_ns, w1)


def reduce(path: str) -> Reduced:
    """Reduce the trace at ``path`` (an ``.xplane.pb``, or one gzipped)."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    host, devices = [], []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append((ev.start_ns, ev.start_ns +
                                     ev.duration_ns, ev.name))
        elif plane.name.startswith("/device:TPU:") and \
                plane.name[len("/device:TPU:"):].isdigit():
            devices.append(plane)
    win = [(a, b) for a, b, n in host if n == WINDOW]
    if len(win) != 1:
        raise ValueError(f"expected one {WINDOW!r} host span, found "
                         f"{len(win)}")
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    w0, w1 = win[0]
    host = [h for h in host if h[2] != WINDOW]
    devices.sort(key=lambda p: int(p.name[len("/device:TPU:"):]))
    busy, chip0 = [], None
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            raise ValueError(f"{plane.name} has no {OPS_LINE!r} line: "
                             f"{sorted(lines)}")
        ops = [(s, e, ev.name) for ev in lines[OPS_LINE].events
               for s, e in [_clip(ev, w0, w1)] if e > s]
        busy.append(_length(_union([(s, e) for s, e, _ in ops])))
        if chip0 is None:
            mods = [] if MODULES_LINE not in lines else [
                (s, e) for ev in lines[MODULES_LINE].events
                for s, e in [_clip(ev, w0, w1)] if e > s]
            chip0 = (ops, sorted(mods))
    ops0, mods0 = chip0
    # each program run belongs to the host span that dispatched it
    span_s, span_calls = collections.Counter(), collections.Counter()
    mod_span = []
    for s, e in mods0:
        n = _innermost(host, s) or "host:other"
        mod_span.append((s, e, n))
        span_s[n] += (e - s) / 1e9
        span_calls[n] += 1
    starts = [m[0] for m in mod_span]
    op_s, kernel_s = collections.Counter(), collections.Counter()
    for s, e, text in ops0:
        name = op_name(text)
        if name in NESTING:
            continue            # its body's ops are events of their own
        i = bisect.bisect_right(starts, s) - 1
        span = mod_span[i][2] if i >= 0 and s < mod_span[i][1] \
            else "host:other"
        op_s[f"{span.removeprefix('bench.')}:{name}"] += (e - s) / 1e9
        if name == "tpu_custom_call":
            kernel_s[span] += (e - s) / 1e9
    idle = _subtract([[w0, w1]], _union([(s, e) for s, e, _ in ops0]))
    idle_by = collections.Counter()
    for s, e in idle:
        n = _innermost(host, (s + e) / 2) or "host:other"
        idle_by[n] += (e - s) / 1e9
    return Reduced(window_s=(w1 - w0) / 1e9,
                   busy_s=sum(busy) / len(busy) / 1e9, chips=len(devices),
                   op_s=dict(op_s), span_s=dict(span_s),
                   span_calls=dict(span_calls), kernel_s=dict(kernel_s),
                   idle_by_host=dict(idle_by))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    r = reduce(argv[0])
    print(json.dumps(dataclasses.asdict(r), indent=1, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
