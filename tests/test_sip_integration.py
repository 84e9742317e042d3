"""SIP end-to-end integration: @sip_jit tune -> cache -> deploy on real
kernels; the full paper workflow at test scale."""

import numpy as np
import pytest

from repro.core import ScheduleCache
from repro.core.jit import SipKernel, TuneConfig
from repro.core.schedule import SearchSpace
from repro.kernels.gemm_fused import ops as gemm_ops
from repro.kernels.gemm_fused import ref as gemm_ref
from repro.kernels.rmsnorm import ops as rms_ops
from repro.kernels.rmsnorm import ref as rms_ref

RNG = np.random.default_rng(3)
QUICK = TuneConfig(rounds=1, t_min=0.25, cooling=1.25, step_samples=1,
                   final_samples=4)


class TestSipJitWorkflow:
    def test_tune_improves_and_stays_correct(self):
        kern = gemm_ops.make()
        x = RNG.standard_normal((32, 64)).astype(np.float32)
        w = RNG.standard_normal((64, 32)).astype(np.float32)
        res = kern.tune([x, w], QUICK)
        assert res[0].improvement >= 0           # never worse than baseline
        np.testing.assert_allclose(np.asarray(kern(x, w)),
                                   np.asarray(gemm_ref.gemm_leaky_relu(x, w)),
                                   rtol=1e-4, atol=1e-4)

    def test_cache_persists_across_instances(self, tmp_path):
        path = str(tmp_path / "cache.json")
        kern = gemm_ops.make(cache=ScheduleCache(path))
        x = RNG.standard_normal((16, 16)).astype(np.float32)
        w = RNG.standard_normal((16, 16)).astype(np.float32)
        kern.tune([x, w], QUICK)
        static = kern.static_of(x, w)
        # a fresh instance (new process analogue) sees the tuned schedule
        kern2 = gemm_ops.make(cache=ScheduleCache(path))
        sched = kern2.schedule_for(static)
        assert sched.order is not None or sched.knobs  # non-default entry
        np.testing.assert_allclose(np.asarray(kern2(x, w)),
                                   np.asarray(gemm_ref.gemm_leaky_relu(x, w)),
                                   rtol=1e-4, atol=1e-4)

    def test_shape_keyed_schedules(self):
        kern = gemm_ops.make()
        a = kern.static_of(np.zeros((16, 32), np.float32),
                           np.zeros((32, 16), np.float32))
        b = kern.static_of(np.zeros((32, 32), np.float32),
                           np.zeros((32, 32), np.float32))
        assert kern.sig_str(a) != kern.sig_str(b)

    def test_wallclock_energy_backend(self):
        """The paper's execution-based feedback also runs (slower, CPU)."""
        kern = rms_ops.make()
        x = RNG.standard_normal((16, 32)).astype(np.float32)
        g = RNG.standard_normal((32,)).astype(np.float32)
        cfg = TuneConfig(rounds=1, t_min=0.5, cooling=1.5, step_samples=0,
                         final_samples=2, energy="wallclock")
        res = kern.tune([x, g], cfg)
        assert np.isfinite(res[0].best_raw) and res[0].best_raw > 0
        np.testing.assert_allclose(np.asarray(kern(x, g)),
                                   np.asarray(rms_ref.rmsnorm(x, g)),
                                   rtol=1e-4, atol=1e-4)

    def test_rmsnorm_tunes(self):
        kern = rms_ops.make()
        x = RNG.standard_normal((32, 64)).astype(np.float32)
        g = RNG.standard_normal((64,)).astype(np.float32)
        res = kern.tune([x, g], QUICK)
        assert res[0].improvement >= 0
        ent = kern.cache.entries(rms_ops.NAME,
                                 kern.sig_str(kern.static_of(x, g)))
        assert ent and all(e.tests_passed for e in ent)


@pytest.mark.parametrize("quarantine", [None, set()])
def test_start_schedule_that_does_not_run_raises(quarantine):
    """A default schedule the compiler refuses is a broken kernel: tuning
    raises with the compiler's error (even where a crash quarantine would
    score a mutated schedule FAILED), and so does an untuned call."""
    def build(schedule, **static):
        def refused(x):
            raise RuntimeError("block shape refused by the compiler")
        return refused

    kern = SipKernel(name="refused", build=build,
                     program_for=lambda s, **st: None,
                     space_for=lambda **st: SearchSpace(),
                     oracle=lambda x: x,
                     signature_fn=lambda x: {"n": int(x.shape[0])})
    x = np.ones(8, np.float32)
    with pytest.raises(RuntimeError, match="start schedule") as err:
        kern.tune([x], TuneConfig(rounds=1, cooling=2.0, final_samples=1,
                                  energy="wallclock"), quarantine=quarantine)
    assert "refused by the compiler" in str(err.value.__cause__)
    with pytest.raises(RuntimeError, match="refused by the compiler"):
        kern(x)
