"""Continuous-batching engine: differential correctness vs single-request
generation, per-slot cache helpers, streaming/stats surface, and the
schedule_cache regression (scope before construction + version bump)."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.models import modules as nn
from repro.models.config import ModelConfig
from repro.serve.engine import ContinuousEngine, Engine, ServeConfig

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                  dtype="float32").validate()
MAX_LEN = 48


@pytest.fixture(scope="module")
def params():
    return nn.unwrap(M.init_lm(jax.random.PRNGKey(0), CFG))


def _requests(rng, n, lens=(4, 7, 11, 16), new=(3, 9)):
    """Mixed-length prompts + decode budgets."""
    return [(rng.integers(0, CFG.vocab, int(rng.choice(lens))).astype(np.int32),
             int(rng.integers(new[0], new[1]))) for _ in range(n)]


@pytest.fixture(scope="module")
def reference(params):
    """Single-request Engine.generate — the paper-style correctness oracle."""
    rng = np.random.default_rng(0)
    reqs = _requests(rng, 6)
    ref = Engine(params, CFG, ServeConfig(max_len=MAX_LEN))
    want = [ref.generate(p[None], n)[0] for p, n in reqs]
    return reqs, want


def _serve(params, reqs, order, capacity, **eng_kw):
    eng = ContinuousEngine(params, CFG,
                           ServeConfig(max_len=MAX_LEN, capacity=capacity),
                           **eng_kw)
    handles = {j: eng.submit(*reqs[j]) for j in order}
    out = eng.run(max_steps=10_000)
    return eng, {j: out[h.uid] for j, h in handles.items()}


class TestDifferential:
    """Greedy continuous batching must be token-identical to single-request
    generation for EVERY request — across arrival orders, batch capacities,
    and mixed prompt lengths (acceptance: >= 3 arrival orderings)."""

    @pytest.mark.parametrize("ordering", ["submit", "reversed", "shuffled"])
    def test_arrival_orders(self, params, reference, ordering):
        reqs, want = reference
        order = {"submit": list(range(len(reqs))),
                 "reversed": list(range(len(reqs)))[::-1],
                 "shuffled": list(np.random.default_rng(3)
                                  .permutation(len(reqs)))}[ordering]
        _, got = _serve(params, reqs, order, capacity=2)
        for j in range(len(reqs)):
            np.testing.assert_array_equal(got[j], want[j],
                                          err_msg=f"request {j} ({ordering})")

    @pytest.mark.parametrize("capacity", [1, 3, 8])
    def test_batch_capacities(self, params, reference, capacity):
        """capacity=1 serializes, capacity=3 churns slots, capacity=8 admits
        everything at once — all token-identical."""
        reqs, want = reference
        _, got = _serve(params, reqs, list(range(len(reqs))), capacity)
        for j in range(len(reqs)):
            np.testing.assert_array_equal(got[j], want[j])

    def test_grouped_prefill_admissions(self, params):
        """Same-length arrivals coalesce into one batched prefill and stay
        identical to batch-1 generation."""
        rng = np.random.default_rng(5)
        reqs = [(rng.integers(0, CFG.vocab, ln).astype(np.int32), 4)
                for ln in (8, 8, 8, 8, 12, 12)]
        ref = Engine(params, CFG, ServeConfig(max_len=MAX_LEN))
        want = [ref.generate(p[None], n)[0] for p, n in reqs]
        eng, got = _serve(params, reqs, list(range(len(reqs))), capacity=6)
        for j in range(len(reqs)):
            np.testing.assert_array_equal(got[j], want[j])
        # 6 admissions, but only 2 distinct prefill shapes -> 2 compiles
        assert eng.stats["admitted"] == 6
        assert eng.stats["prefill_compiles"] == 2

    def test_eos_truncation_matches(self, params, reference):
        reqs, want = reference
        # an eos id every reference output contains early keeps the test
        # meaningful; each request stops at ITS first occurrence
        eos = int(want[0][1])
        ref = Engine(params, CFG, ServeConfig(max_len=MAX_LEN))
        want_eos = [ref.generate(p[None], n, eos_id=eos)[0]
                    for p, n in reqs]
        eng = ContinuousEngine(params, CFG,
                               ServeConfig(max_len=MAX_LEN, capacity=3))
        hs = [eng.submit(p, n, eos_id=eos) for p, n in reqs]
        out = eng.run(max_steps=10_000)
        for j, h in enumerate(hs):
            np.testing.assert_array_equal(out[h.uid], want_eos[j])

    def test_ssm_family(self):
        """Per-slot state splicing for a recurrent (cacheless-attention)
        family."""
        cfg = ModelConfig(name="s", family="ssm", n_layers=2, d_model=64,
                          n_heads=0, n_kv_heads=0, d_ff=0, vocab=128,
                          ssm_state=16, ssm_headdim=32, ssm_chunk=8,
                          dtype="float32").validate()
        p = nn.unwrap(M.init_lm(jax.random.PRNGKey(1), cfg))
        rng = np.random.default_rng(6)
        reqs = [(rng.integers(0, 128, int(rng.choice([6, 10]))).astype(np.int32),
                 int(rng.integers(3, 6))) for _ in range(4)]
        ref = Engine(p, cfg, ServeConfig(max_len=24))
        want = [ref.generate(pr[None], n)[0] for pr, n in reqs]
        eng = ContinuousEngine(p, cfg, ServeConfig(max_len=24, capacity=2))
        hs = [eng.submit(pr, n) for pr, n in reqs]
        out = eng.run(max_steps=10_000)
        for j, h in enumerate(hs):
            np.testing.assert_array_equal(out[h.uid], want[j])


class TestEngineSurface:
    def test_streaming_and_stats(self, params, reference):
        reqs, _ = reference
        streamed: dict[int, list[int]] = {}
        eng = ContinuousEngine(
            params, CFG, ServeConfig(max_len=MAX_LEN, capacity=2),
            on_token=lambda r, t: streamed.setdefault(r.uid, []).append(t))
        hs = [eng.submit(p, n) for p, n in reqs]
        out = eng.run(max_steps=10_000)
        for h in hs:
            assert streamed[h.uid] == list(out[h.uid])   # stream == final
            assert h.done and h.first_token_at is not None
        s = eng.stats
        assert s["completed"] == s["submitted"] == len(reqs)
        assert s["tokens_out"] == sum(len(o) for o in out.values())
        assert 0 < s["occupancy_sum"] <= 2 * s["steps"]
        m = eng.metrics()
        assert m["queue_depth"] == 0 and m["slot_occupancy"] == 0
        assert m["tokens_per_s"] > 0 and 0 < m["prefill_frac"] < 1

    def test_submit_validation(self, params):
        eng = ContinuousEngine(params, CFG,
                               ServeConfig(max_len=16, capacity=2))
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(np.zeros(4, np.int32), 0)
        with pytest.raises(ValueError, match="exceeds max_len"):
            eng.submit(np.zeros(10, np.int32), 8)

    def test_min_prompt_for_conv_families(self):
        cfg = ModelConfig(name="s", family="ssm", n_layers=1, d_model=64,
                          n_heads=0, n_kv_heads=0, d_ff=0, vocab=128,
                          ssm_state=16, ssm_headdim=32, ssm_chunk=8,
                          dtype="float32").validate()
        p = nn.unwrap(M.init_lm(jax.random.PRNGKey(2), cfg))
        eng = ContinuousEngine(p, cfg, ServeConfig(max_len=16, capacity=1))
        with pytest.raises(ValueError, match="conv receptive field"):
            eng.submit(np.zeros(1, np.int32), 2)


class TestSlotCacheHelpers:
    """models/model.py per-slot insert/evict on the raw cache pytree."""

    def test_axes_discovery_and_roundtrip(self, params):
        ex = {"tokens": np.zeros((1, 8), np.int32)}
        caches, axes = M.alloc_slot_caches(params, CFG, 3, MAX_LEN, ex)
        assert axes["k"] == 1 and axes["v"] == 1
        assert axes["len"] == M.SLOT_AXIS_SHARED
        assert caches["k"].shape[1] == 3 and caches["len"].shape == (2, 3)

        rng = np.random.default_rng(7)
        toks = jnp.asarray(rng.integers(0, CFG.vocab, (1, 8)), jnp.int32)
        _, one = M.prefill(params, {"tokens": toks}, CFG, MAX_LEN)
        caches = M.insert_slot(caches, one, 1, axes)
        np.testing.assert_array_equal(np.asarray(caches["k"][:, 1]),
                                      np.asarray(one["k"][:, 0]))
        np.testing.assert_array_equal(np.asarray(caches["len"][:, 1]),
                                      np.asarray(one["len"]))
        assert int(caches["len"][:, 0].max()) == 0    # other slots untouched

        caches = M.evict_slot(caches, 1, axes)
        assert int(caches["len"][:, 1].max()) == 0    # masked empty
        # KV payload is left in place; the length reset is what invalidates

    def test_grouped_insert_matches_sequential(self, params):
        ex = {"tokens": np.zeros((1, 8), np.int32)}
        caches, axes = M.alloc_slot_caches(params, CFG, 4, MAX_LEN, ex)
        rng = np.random.default_rng(8)
        toks = jnp.asarray(rng.integers(0, CFG.vocab, (2, 8)), jnp.int32)
        _, grp = M.prefill(params, {"tokens": toks}, CFG, MAX_LEN)
        got = M.insert_slots(caches, grp, jnp.asarray([3, 0]), axes)
        for g, slot in enumerate([3, 0]):
            np.testing.assert_array_equal(np.asarray(got["k"][:, slot]),
                                          np.asarray(grp["k"][:, g]))
            np.testing.assert_array_equal(np.asarray(got["len"][:, slot]),
                                          np.asarray(grp["len"]))


class TestScheduleCacheRegression:
    def test_scope_before_construction_survives_version_bump(self, params):
        """A schedule_cache scope entered BEFORE engine construction must be
        honored by kernel resolution inside the serve loop, including after
        tuning bumps ScheduleCache.version mid-flight (late-binding handles +
        version-synced resolution memos)."""
        from repro.core.cache import ScheduleCache
        from repro.core.jit import TuneConfig
        from repro.core.registry import registry, schedule_cache
        from repro.kernels.flash_attention import ops as fa_ops

        store = ScheduleCache()
        cfg_p = dataclasses.replace(CFG, use_pallas=True)
        rng = np.random.default_rng(9)
        prompt = rng.integers(0, CFG.vocab, 16).astype(np.int32)
        with schedule_cache(store):
            eng = ContinuousEngine(params, cfg_p,
                                   ServeConfig(max_len=MAX_LEN, capacity=2))
            h1 = eng.submit(prompt, 4)
            out1 = eng.run(max_steps=10_000)[h1.uid]

            name = fa_ops.variant_name(True, None)
            kern = registry.get(name)
            assert kern.cache is store      # scope bound the serving instance
            v0 = store.version

            # tune the serving shape (prefill: B=1, H=4/KV=2, S=16, D=16)
            example = [rng.standard_normal((1, 4, 16, 16)).astype(np.float32),
                       rng.standard_normal((1, 2, 16, 16)).astype(np.float32),
                       rng.standard_normal((1, 2, 16, 16)).astype(np.float32)]
            kern.tune(example, TuneConfig(rounds=1, t_min=0.3, cooling=1.3,
                                          step_samples=1, final_samples=2))
            assert store.version > v0

            # deployment path now serves the TUNED schedule from the store
            static = kern.static_of(*example)
            tuned = store.best(name, kern.sig_str(static))
            assert tuned is not None
            kern(*example)                  # resolve post-bump
            assert kern._resolved_version == store.version

            # the engine keeps serving correctly after the bump: a repeat of
            # the same request (semantics-preserving schedule swap) and a new
            # prompt length (fresh trace resolves through the same store)
            h2 = eng.submit(prompt, 4)
            out2 = eng.run(max_steps=10_000)[h2.uid]
            np.testing.assert_array_equal(out2, out1)
            h3 = eng.submit(prompt[:12], 4)
            eng.run(max_steps=10_000)
            assert registry.get(name) is kern   # still the scope's instance
            assert kern._resolved_version == store.version
