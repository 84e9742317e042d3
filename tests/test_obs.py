"""repro.obs telemetry: metrics registry scoping + histogram edges, tracer
span nesting + Chrome export + validation, spans timed once, the compile
counter and tuner spans, engine instrumentation (registry-backed stats,
zero-run metrics guards, the step's spans), and the WorkloadRecorder ->
TuningSession round trip."""

import json
import math
import threading

import numpy as np
import pytest

import jax

from repro import obs
from repro.launch import obsreport
from repro.models import model as M
from repro.models import modules as nn
from repro.models.config import ModelConfig
from repro.obs.metrics import (Histogram, MetricsRegistry, active_registry,
                               exponential_edges, metrics_scope)
from repro.obs.recorder import WorkloadKey, WorkloadRecorder
from repro.obs.trace import (Tracer, active_tracer, load_trace, span,
                             tracing, validate_events, validate_trace)
from repro.serve.engine import ContinuousEngine, ServeConfig

CFG = ModelConfig(name="t", family="dense", n_layers=1, d_model=32,
                  n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
                  dtype="float32").validate()


@pytest.fixture(scope="module")
def params():
    return nn.unwrap(M.init_lm(jax.random.PRNGKey(0), CFG))


# ================================================================= metrics
class TestMetrics:
    def test_counter_gauge_basics(self):
        reg = MetricsRegistry()
        c = reg.counter("c")
        c.inc()
        c.inc(2)
        assert c.value == 3 and isinstance(c.value, int)
        c.inc(0.5)
        assert c.value == 3.5
        g = reg.gauge("g")
        g.set(7)
        assert g.value == 7.0
        assert reg.counter("c") is c          # get-or-create shares
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("c")

    def test_histogram_under_and_overflow(self):
        """Values below the first and above the last bucket edge are counted
        in the open end buckets, never dropped."""
        h = Histogram("h", edges=[1.0, 2.0, 4.0])
        for v in (0.1, 0.5):                  # below first edge
            h.record(v)
        for v in (100.0, 9e9):                # above last edge
            h.record(v)
        h.record(3.0)
        assert h.count == 5
        snap = h.snapshot()
        assert snap["counts"][0] == 2         # underflow bucket
        assert snap["counts"][-1] == 2        # overflow bucket
        assert snap["min"] == 0.1 and snap["max"] == 9e9
        # percentiles stay finite and within observed range
        for q in (0, 50, 95, 99, 100):
            p = h.percentile(q)
            assert math.isfinite(p) and 0.1 <= p <= 9e9
        h.record(float("inf"))                # non-finite: ignored
        h.record(float("nan"))
        assert h.count == 5

    def test_histogram_empty_and_validation(self):
        h = Histogram("h", edges=[1.0, 2.0])
        assert h.percentile(50) == 0.0 and h.mean == 0.0
        with pytest.raises(ValueError, match="strictly increasing"):
            Histogram("bad", edges=[2.0, 1.0])
        with pytest.raises(ValueError, match="strictly increasing"):
            Histogram("bad", edges=[1.0, 1.0])
        edges = exponential_edges(1e-3, 10.0, 8)
        assert len(edges) == 8 and list(edges) == sorted(edges)

    def test_histogram_concurrent_recording(self):
        """The engine records from its streaming-callback thread while the
        driver thread reads — no lost updates under contention."""
        h = Histogram("h", edges=list(exponential_edges(1e-3, 10.0, 12)))
        n_threads, per_thread = 8, 500

        def work(seed):
            rng = np.random.default_rng(seed)
            for _ in range(per_thread):
                h.record(float(rng.uniform(1e-4, 20.0)))

        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert h.count == n_threads * per_thread
        assert sum(h.snapshot()["counts"]) == n_threads * per_thread

    def test_registry_isolation_nested_scopes(self):
        """Nested metrics_scope levels are isolated from each other AND from
        the process default; innermost wins; exit restores."""
        default = active_registry()
        with metrics_scope() as outer:
            assert active_registry() is outer
            outer.counter("x").inc()
            with metrics_scope() as inner:
                assert active_registry() is inner
                inner.counter("x").inc(10)
                assert inner.counter("x").value == 10
            assert active_registry() is outer
            assert outer.counter("x").value == 1
        assert active_registry() is default
        assert "x" not in default or default.counter("x").value not in (1, 10)

    def test_snapshot_save_json(self, tmp_path):
        reg = MetricsRegistry()
        reg.counter("a").inc(3)
        reg.histogram("h", edges=[1.0]).record(0.5)
        path = str(tmp_path / "m.json")
        reg.save_json(path)
        with open(path) as f:
            snap = json.load(f)
        assert snap["a"] == {"type": "counter", "value": 3}
        assert snap["h"]["count"] == 1 and "p99" in snap["h"]


# =================================================================== trace
class TestTracer:
    def test_nested_spans_validate(self, tmp_path):
        tr = Tracer()
        with tr.span("outer", kind="test"):
            with tr.span("inner"):
                tr.instant("tick", i=1)
            tr.counter("energy", {"e": 0.5})
        events = tr.events()
        assert [e["ph"] for e in events] == ["I", "X", "C", "X"]
        assert validate_events(events) == []
        chrome = tr.to_chrome()
        assert len(chrome["traceEvents"]) == 4
        # round-trip both file forms
        for name in ("t.json", "t.jsonl"):
            p = str(tmp_path / name)
            tr.save(p)
            assert validate_trace(p) == []
            assert len(load_trace(p)) == 4
        # chrome form is strictly-valid JSON with spans nested by time
        with open(str(tmp_path / "t.json")) as f:
            loaded = json.load(f)
        outer, = [e for e in loaded["traceEvents"] if e["name"] == "outer"]
        inner, = [e for e in loaded["traceEvents"] if e["name"] == "inner"]
        assert outer["ts"] <= inner["ts"]
        assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 0.01

    def test_span_args_attach_results(self):
        tr = Tracer()
        with tr.span("s", a=1) as sp:
            sp["b"] = 2
        ev, = tr.events()
        assert ev["args"] == {"a": 1, "b": 2}

    def test_nonfinite_args_stay_strict_json(self):
        tr = Tracer()
        tr.instant("i", bad=float("inf"), worse=float("nan"), ok=1.5)
        line = json.dumps(tr.events()[0])     # must not raise under strict
        ev = json.loads(line)
        assert ev["args"]["ok"] == 1.5
        assert isinstance(ev["args"]["bad"], str)

    def test_validator_catches_malformed(self):
        assert validate_events([{"ph": "Z", "name": "x", "ts": 0.0,
                                 "pid": 1, "tid": 1}])
        assert validate_events([{"ph": "X", "name": "x", "ts": -1.0,
                                 "dur": 1.0, "pid": 1, "tid": 1}])
        assert validate_events([{"ph": "X", "name": "x", "ts": 0.0,
                                 "pid": 1, "tid": 1}])   # missing dur
        # overlapping, non-nesting spans on one track
        bad = [{"ph": "X", "name": "a", "ts": 0.0, "dur": 10.0,
                "pid": 1, "tid": 1, "args": {}},
               {"ph": "X", "name": "b", "ts": 5.0, "dur": 10.0,
                "pid": 1, "tid": 1, "args": {}}]
        assert any("overlaps" in e for e in validate_events(bad))

    def test_scope_helpers_noop_when_inactive(self):
        assert active_tracer() is None
        with span("s", a=1) as sp:            # must not raise
            sp["b"] = 2
        obs.instant("i")
        with tracing() as tr:
            assert active_tracer() is tr
            with span("s"):
                pass
            assert len(tr.events()) == 1
        assert active_tracer() is None

    def test_threaded_spans_get_own_tracks(self):
        tr = Tracer()

        def work():
            with tr.span("child"):
                pass

        with tracing(tr):
            t = threading.Thread(target=work)
            with tr.span("main"):
                t.start()
                t.join()
        events = tr.events()
        tids = {e["tid"] for e in events}
        assert len(tids) == 2                 # one track per thread
        assert validate_events(events) == []


    def test_span_times_once_on_either_sink(self):
        """A span measures its interval once: with no tracer it records
        nothing and still reports ``dur_s``; with one, the event's
        duration is that same measurement."""
        assert active_tracer() is None
        with span("off", a=1) as sp:
            sp["b"] = 2
        assert sp.dur_s > 0 and sp.args == {"a": 1, "b": 2}
        tr = Tracer()
        with tracing(tr):
            with span("on") as sp:
                pass
        ev, = tr.events()
        assert ev["dur"] == pytest.approx(sp.dur_s * 1e6, abs=0.01)

    def test_nested_compile_events_count_once(self):
        """JAX reports a trace that traced inner jitted functions after
        them, each with its own span: the wall time counts once."""
        from repro.obs.trace import _CompileWatch
        watch = _CompileWatch()
        with metrics_scope() as reg, tracing() as tr:
            for start, end in ((1.0, 2.0), (3.0, 4.0), (0.0, 5.0),
                               (6.0, 7.0), (7.5, 8.0)):
                watch("/jax/core/compile/jaxpr_trace_duration", start, end,
                      fun_name="f")
            watch("/jax/compilation_cache/cache_hits", 0.0, 9.0)
        assert watch.seconds == reg.counter("jax.compile_s").value == 6.5
        assert reg.counter("jax.compiles").value == 5
        assert [e["args"]["s"] for e in tr.events()] == \
            [1.0, 1.0, 5.0, 1.0, 0.5]

    def test_tune_counts_compiles_and_traces_evals(self):
        """A tune call counts JAX's compile events into the active
        registry, its own compile seconds as ``tune.compile_s``, and each
        energy evaluation the memo misses as a ``tune.eval`` span inside
        its round."""
        from repro.core.cache import ScheduleCache
        from repro.core.jit import TuneConfig
        from repro.core.registry import registry
        from repro.kernels.gemm_fused import ops as gemm_ops
        rng = np.random.default_rng(0)
        x = rng.standard_normal((16, 32)).astype(np.float32)
        w = rng.standard_normal((32, 16)).astype(np.float32)
        kern = registry.get(gemm_ops.NAME, cache=ScheduleCache())
        with metrics_scope() as reg, tracing() as tr:
            res, = kern.tune([x, w], TuneConfig(
                rounds=1, t_min=0.25, cooling=1.5, step_samples=1,
                final_samples=2))
        assert reg.counter("jax.compiles").value > 0
        assert 0 < reg.counter("tune.compile_s").value \
            <= reg.counter("jax.compile_s").value
        events = tr.events()
        assert validate_events(events) == []
        evals = [e for e in events if e["name"] == "tune.eval"]
        rnd, = [e for e in events if e["name"] == "tune.round"]
        assert len(evals) == res.cache_stats["misses"]
        assert all(rnd["ts"] <= e["ts"] and e["ts"] + e["dur"]
                   <= rnd["ts"] + rnd["dur"] + 0.01 for e in evals)
        assert any(e["name"] == "jax.compile" for e in events)


# ====================================================== engine integration
class TestEngineObservability:
    def test_zero_run_metrics_all_finite(self, params):
        """Satellite regression: a never-stepped engine reports well-defined
        0.0 rates — no ZeroDivisionError, no inf/NaN."""
        eng = ContinuousEngine(params, CFG,
                               ServeConfig(max_len=16, capacity=2))
        m = eng.metrics()
        assert set(m) == {"queue_depth", "slot_occupancy", "mean_occupancy",
                          "mean_queue_depth", "prefill_s", "decode_s",
                          "prefill_frac", "tokens_per_s",
                          "decode_tokens_per_s"}
        for k, v in m.items():
            assert math.isfinite(v), (k, v)
        assert m["tokens_per_s"] == 0.0
        assert m["decode_tokens_per_s"] == 0.0
        assert m["prefill_frac"] == 0.0
        assert m["mean_occupancy"] == 0.0

    def test_stats_and_histograms_from_registry(self, params):
        eng = ContinuousEngine(params, CFG,
                               ServeConfig(max_len=24, capacity=2))
        rng = np.random.default_rng(0)
        hs = [eng.submit(rng.integers(0, CFG.vocab, 5).astype(np.int32), 3)
              for _ in range(3)]
        out = eng.run(max_steps=10_000)
        assert all(len(out[h.uid]) == 3 for h in hs)
        s = eng.stats
        assert s["submitted"] == s["completed"] == 3
        assert s["tokens_out"] == 9
        # the registry is the source of truth behind the stats dict
        assert eng.obs.counter("serve.tokens_out").value == 9
        assert eng.obs.histogram("serve.ttft_s").count == 3
        assert eng.obs.histogram("serve.inter_token_s").count == 6
        snap = eng.obs.snapshot()
        assert snap["serve.prefill_call_s"]["count"] >= 1
        assert snap["serve.decode_step_s"]["count"] >= 1
        m = eng.metrics()
        assert m["tokens_per_s"] > 0 and 0 < m["prefill_frac"] < 1

    def test_engines_do_not_share_counters(self, params):
        e1 = ContinuousEngine(params, CFG,
                              ServeConfig(max_len=16, capacity=1))
        e2 = ContinuousEngine(params, CFG,
                              ServeConfig(max_len=16, capacity=1))
        e1.submit(np.zeros(4, np.int32), 2)
        e1.run(max_steps=100)
        assert e1.stats["submitted"] == 1
        assert e2.stats["submitted"] == 0

    def test_reset_stats_keeps_compiles(self, params):
        eng = ContinuousEngine(params, CFG,
                               ServeConfig(max_len=16, capacity=1))
        eng.submit(np.zeros(4, np.int32), 2)
        eng.run(max_steps=100)
        compiles = eng.stats["prefill_compiles"]
        assert compiles >= 1
        eng.reset_stats()
        s = eng.stats
        assert s["prefill_compiles"] == compiles
        assert s["tokens_out"] == 0 and s["steps"] == 0
        assert eng.obs.histogram("serve.ttft_s").count == 0

    def test_contiguous_step_children(self, params):
        """The contiguous-cache path has the paged path's step tree, less
        the chunks: admit, a prefill and a decode each with a ``.wait``,
        and emission."""
        tr = Tracer()
        with tracing(tr):
            eng = ContinuousEngine(params, CFG,
                                   ServeConfig(max_len=16, capacity=2))
            eng.submit(np.zeros(4, np.int32), 3)
            eng.run(max_steps=100)
        spans = sorted(((e["ts"], e["ts"] + e["dur"], e["name"])
                        for e in tr.events() if e["ph"] == "X"),
                       key=lambda x: (x[0], -x[1]))
        parent, stack = {}, []
        for s, e, n in spans:
            while stack and stack[-1][1] <= s:
                stack.pop()
            parent.setdefault(n, set()).add(stack[-1][2] if stack else None)
            stack.append((s, e, n))
        assert parent == {
            "serve.step": {None}, "serve.admit": {"serve.step"},
            "serve.prefill": {"serve.step"},
            "serve.prefill.wait": {"serve.prefill"},
            "serve.decode": {"serve.step"},
            "serve.decode.wait": {"serve.decode"},
            "serve.emit": {"serve.step"}}
        assert eng.stats["prefill_starts"] == 1
        assert 0 < eng.stats["host_s"]

    def test_serve_run_emits_valid_spans(self, params, tmp_path):
        tr = Tracer()
        with tracing(tr):
            eng = ContinuousEngine(params, CFG,
                                   ServeConfig(max_len=16, capacity=2))
            eng.submit(np.zeros(4, np.int32), 2)
            eng.submit(np.ones(6, np.int32), 2)
            eng.run(max_steps=100)
        events = tr.events()
        names = {e["name"] for e in events}
        assert "serve.prefill" in names and "serve.decode" in names
        assert validate_events(events) == []
        p = str(tmp_path / "serve.json")
        tr.save(p)
        assert validate_trace(p) == []


# ======================================================== workload recorder
class TestWorkloadRecorder:
    def test_engine_hook_and_roundtrip(self, params, tmp_path):
        rec = WorkloadRecorder()
        eng = ContinuousEngine(params, CFG,
                               ServeConfig(max_len=24, capacity=2),
                               recorder=rec)
        rng = np.random.default_rng(1)
        for plen in (5, 5, 8):
            eng.submit(rng.integers(0, CFG.vocab, plen).astype(np.int32), 3)
        eng.run(max_steps=10_000)
        mix = rec.mix()
        kinds = {k.kind for k in mix}
        assert kinds == {"submit", "prefill", "decode"}
        prefill_rows = sum(k.batch * n for k, n in mix.items()
                           if k.kind == "prefill")
        assert prefill_rows == 3              # every request prefilled once
        path = str(tmp_path / "live.jsonl")
        rec.save(path)
        assert obsreport.validate_workloads(path) == []
        loaded = WorkloadRecorder.load(path)
        assert loaded.mix() == mix
        assert loaded.summary()["submitted"] == 3

    def test_to_workloads_into_tuning_session(self, tmp_path):
        """Acceptance: recorder output round-trips into a TuningSession
        workload list — tuned entries land in the schedule cache."""
        from repro.core.cache import ScheduleCache
        from repro.core.jit import TuneConfig
        from repro.kernels.gemm_fused import ops as gemm_ops
        from repro.tuning.session import TuningSession

        rec = WorkloadRecorder()
        rec.record("prefill", prompt_len=16, batch=2, dtype="float32",
                   occupancy=2)
        rec.record("prefill", prompt_len=16, batch=2, dtype="float32",
                   occupancy=1)
        rec.record("decode", batch=4, dtype="float32", occupancy=3)
        path = str(tmp_path / "live.jsonl")
        rec.save(path)

        def gemm_args_for(key: WorkloadKey):
            if key.kind != "prefill":
                return None                   # decode mix tunes other kernels

            def make_args(rng):
                x = rng.standard_normal((key.prompt_len, 32)).astype(
                    np.float32)
                w = rng.standard_normal((32, 16)).astype(np.float32)
                return [x, w]
            return make_args

        wls = WorkloadRecorder.load(path).to_workloads(gemm_args_for)
        assert len(wls) == 1 and wls[0].name.startswith("live_prefill_p16")
        assert wls[0].suites == ("live",)
        cache = ScheduleCache()
        session = TuningSession(cache=cache, config=TuneConfig(
            rounds=1, t_min=0.5, cooling=1.4, step_samples=0,
            final_samples=2))
        run = session.run_workload(gemm_ops.NAME, wls[0])
        assert run.workload == wls[0].name
        assert cache.entries(gemm_ops.NAME, run.signature)

    def test_record_cap_keeps_mix_complete(self):
        rec = WorkloadRecorder(max_records=3)
        for _ in range(10):
            rec.record("decode", batch=1, occupancy=1)
        assert len(rec) == 3 and rec.dropped == 7
        assert sum(rec.mix().values()) == 10  # aggregation never truncated


# =============================================================== obsreport
class TestObsreport:
    def test_validate_cli_ok_and_invalid(self, tmp_path, capsys):
        tr = Tracer()
        with tr.span("a"):
            pass
        good = str(tmp_path / "good.json")
        tr.save(good)
        assert obsreport.main([good, "--validate"]) == 0
        bad = str(tmp_path / "bad.json")
        with open(bad, "w") as f:
            json.dump({"traceEvents": [{"ph": "X", "name": "x", "ts": 0.0,
                                        "pid": 1, "tid": 1}]}, f)
        assert obsreport.main([bad, "--validate"]) == 1
        out = capsys.readouterr().out
        assert "INVALID" in out

    def test_summary_cli(self, tmp_path, capsys):
        tr = Tracer()
        with tr.span("tune.round", kernel="k"):
            tr.counter("search.energy/chain0", {"energy": 0.9})
            tr.counter("search.energy/chain0", {"energy": 0.7})
        p = str(tmp_path / "t.json")
        tr.save(p)
        reg = MetricsRegistry()
        reg.histogram("h").record(0.01)
        mp = str(tmp_path / "m.json")
        reg.save_json(mp)
        assert obsreport.main([p, "--metrics-json", mp]) == 0
        out = capsys.readouterr().out
        assert "tune.round" in out and "search.energy/chain0" in out
        assert "p99" in out or "p95" in out

    def test_workloads_validation_catches_bad_lines(self, tmp_path):
        p = str(tmp_path / "w.jsonl")
        with open(p, "w") as f:
            f.write(json.dumps({"kind": "warp", "t": 0.0}) + "\n")
        errs = obsreport.validate_workloads(p)
        assert errs and any("bad kind" in e for e in errs)
