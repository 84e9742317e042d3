"""The program's own spans name the idle device time of a recorded trace,
while the trace reduction keeps giving programs and operations to the
harness's ``bench.*`` spans alone.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import json
import os

import pytest

from bench import idle_spans, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
TRACE = os.path.join(DATA, "chat_spans.xplane.pb.gz")


def test_idle_gaps_are_named_by_program_spans():
    """A ``--trace 1`` run of qwen3-1.7b.chat on one TPU v5e, with the
    engine's ``serve.*`` spans on the profiler's clock; the gaps were read
    from it once and are pinned."""
    idle = idle_spans.idle_by_span(TRACE)
    with open(os.path.join(DATA, "chat_spans.idle.json")) as f:
        want = json.load(f)
    assert idle.keys() == want.keys()
    for name, v in want.items():
        assert idle[name] == pytest.approx(v, rel=1e-9)
    r = trace_reduce.reduce(TRACE)
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s,
                                               rel=1e-6)
    # at least 90% of the idle time lies under a serve.* span
    assert idle_spans.program_share(idle) >= 0.9
    assert sum(v for k, v in idle.items() if k.startswith("serve.")) >= \
        0.9 * sum(idle.values())


def test_programs_stay_with_the_harness_spans():
    """The program spans leave ``trace_reduce``'s attribution, and every
    metric read from it, on the ``bench.*`` spans."""
    r = trace_reduce.reduce(TRACE)
    for attr in ("span_s", "span_calls", "kernel_s", "idle_by_host"):
        names = getattr(r, attr)
        assert names and all(k.startswith("bench.") or k == "host:other"
                             for k in names), (attr, names)
    assert all(k.split(":", 1)[0] in ("decode", "chunk", "step",
                                      "host") for k in r.op_s)
