"""The yardstick's arithmetic against numbers counted by hand, the trace
reduction against a small recorded trace, and the traffic generator's
promise that a seed changes order and content but not the amount of work.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench import flops, peaks, traffic, weights
from bench.tests import small

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _spec(name: str) -> weights.Spec:
    with open(small.ROOT / "bench" / "configs" / name) as f:
        return weights.Spec.from_config(json.load(f))


def test_qwen3_decode_token_flops():
    # per layer: q and o 2048*16*128 each, k and v 2048*8*128 each,
    # MLP 3*2048*6144 -> 50,331,648 multiply-adds; 28 layers
    s = _spec("qwen3-1.7b.json")
    assert flops.matmul_params_per_layer(s) == 50_331_648
    want = (2 * 50_331_648 * 28            # matmuls
            + 4 * 16 * 128 * 1000 * 28     # QK^T and PV over 1000 keys
            + 2 * 2048 * 151_936)          # output head
    assert flops.token_flops(s, 1000) == want == 3_670_278_144


def test_qwen3_chunk_flops():
    # 256 tokens at positions 512..767: keys 256*512 + 256*257/2 = 163,968
    s = _spec("qwen3-1.7b.json")
    want = 2 * 50_331_648 * 28 * 256 + 4 * 16 * 128 * 163_968 * 28
    assert flops.chunk_flops(s, 512, 256, last=False) == want
    assert flops.chunk_flops(s, 512, 256, last=True) == \
        want + 2 * 2048 * 151_936


def test_causal_attention_and_gather_bytes():
    # (1, 16, 2048, 128): 2048*2049/2 = 2,098,176 unmasked pairs per head
    assert flops.causal_attention_flops(1, 16, 2048, 128) == \
        4 * 16 * 128 * 2_098_176 == 17_188_257_792
    assert flops.attention_bytes(1, 16, 8, 2048, 128, 2) == \
        2 * 2048 * 128 * (2 * 16 + 2 * 8)
    # 32 slots x 160 pages x 16 x 8 heads x 128 x 2 B, read and written
    assert flops.gather_bytes(32, 160, 16, 8, 128, 2) == 335_544_320


def test_unknown_device_is_an_error():
    assert peaks.for_kind("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError):
        peaks.for_kind("cpu")


def test_seed_changes_content_not_work():
    mix = {"kind": "poisson", "rate_per_s": 2.0, "order_seed": 12,
           "prompt_tokens": {"median": 768, "sigma": 0.6, "min": 272,
                             "max": 2048},
           "output_tokens": {"median": 128, "sigma": 0.7, "min": 32,
                             "max": 512}}
    a = traffic.poisson(mix, 30, 1000, np.random.default_rng(1))
    b = traffic.poisson(mix, 30, 1000, np.random.default_rng(2 ** 33 + 5))
    again = traffic.poisson(mix, 30, 1000, np.random.default_rng(1))
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, again))
    sched = lambda rs: [(r.due, len(r.prompt), r.max_new) for r in rs]
    assert sched(a) == sched(b)
    assert any((x.prompt != y.prompt).any() for x, y in zip(a, b))
    # the lengths are quantiles of the stated distributions
    lens = sorted(len(r.prompt) for r in a)
    assert lens != [len(r.prompt) for r in a]
    assert 272 <= lens[0] and lens[-1] <= 2048
    assert abs(np.median(lens) - 768) < 40
    ramp = traffic.poisson(mix, 30, 1000, np.random.default_rng(1), "ramp")
    assert sched(ramp) != sched(a)


def test_weights_are_the_same_stacked_or_by_layer():
    import jax
    import jax.numpy as jnp
    s = weights.Spec.from_config(small.CONFIG)
    key = weights.seed_key(2 ** 31 + 7)
    stacked = weights.program_params(key, s, jnp.bfloat16)
    for l in range(s.layers):
        one = weights.layer(key, l, s, jnp.bfloat16)
        got = jax.tree.map(lambda x: x[l], stacked["blocks"])
        assert jax.tree.all(jax.tree.map(
            lambda a, b: bool((a == b).all()), got, one))


def test_tied_head_is_the_embedding():
    """A configuration that ties its head serves the embedding's
    transpose in the program's separate head."""
    import jax.numpy as jnp
    s = _spec("qwen3-1.7b.json")
    assert s.tied
    small_tied = weights.Spec.from_config(small.CONFIG)
    assert small_tied.tied
    t = weights.top(weights.seed_key(2 ** 31 + 9), small_tied, jnp.bfloat16)
    assert (t["lm_head"] == t["embed"].T).all()
    untied = weights.Spec.from_config(
        dict(small.CONFIG, tie_word_embeddings=False))
    t = weights.top(weights.seed_key(2 ** 31 + 9), untied, jnp.bfloat16)
    assert t["lm_head"].shape == t["embed"].T.shape
    assert not (t["lm_head"] == t["embed"].T).all()


def test_trace_reduction_of_a_recorded_trace():
    """A trace recorded on one TPU v5e by a ``--trace 1`` run of
    qwen3-1.7b.chat; the totals were read from it once and are pinned."""
    from bench import trace_reduce
    path = os.path.join(DATA, "chat.xplane.pb.gz")
    r = trace_reduce.reduce(path)
    with open(os.path.join(DATA, "chat.reduced.json")) as f:
        want = json.load(f)
    assert r.chips == want["chips"] == 1
    assert r.window_s == pytest.approx(want["window_s"], rel=1e-9)
    assert r.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert 0 < r.busy_s <= r.window_s
    assert r.span_calls == want["span_calls"]
    for k in ("span_s", "kernel_s", "idle_by_host"):
        got = getattr(r, k)
        assert got.keys() == want[k].keys()
        for name, v in want[k].items():
            assert got[name] == pytest.approx(v, rel=1e-9)
    # the programs, and the idle gaps, cover the window at most once
    assert sum(r.idle_by_host.values()) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
    assert sum(r.op_s.values()) <= r.busy_s * (1 + 1e-9)


def test_sessions_ttft_tail_is_per_layer():
    """The sessions cell reports its TTFT tail per layer, and every
    per-layer metric there moves an end-to-end metric it reports."""
    from bench import run
    with open(small.ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    _, _, e2e, layer = run.select(bench, "qwen3-1.7b.sessions")
    reported = {m["name"] for m in e2e}
    assert "ttft_p95_ms" not in reported
    assert "ttft_p95_ms.sessions" in {m["name"] for m in layer}
    for cell in bench["workloads"]:
        _, _, e2e, _ = run.select(bench, cell["name"])
        reported = {m["name"] for m in e2e}
        for m in bench["per_layer"]:
            if cell["name"] in m.get("workloads", [cell["name"]]):
                assert m["moves"] in reported, (cell["name"], m["name"])


def test_sessions_ttft_leaves_out_profiler_waits():
    from types import SimpleNamespace
    from bench import run
    read = run.reader(small.ROOT, "ttft_p95_ms.sessions")
    # 20 requests at 0.1 s; one waited through the profiler's start
    reqs = [(float(i), i + 0.1) for i in range(20)] + [(30.0, 33.0)]
    w = SimpleNamespace(requests=reqs, profiler_s=[(30.5, 32.9)])
    assert abs(read(w) - 100.0) < 1e-6
    w.profiler_s = []
    assert read(w) > 100.0
    assert read(SimpleNamespace(requests=[], profiler_s=[])) is None
