"""Per-kernel allclose sweeps vs the pure-jnp oracles (interpret mode on CPU).

Every Pallas kernel is swept over shapes / dtypes / schedule knobs and every
legal instruction-order perturbation class we care about, asserting
equivalence with ref.py — the same contract SIP's probabilistic testing
enforces at search time.
"""

import subprocess
import sys

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core.schedule import Schedule
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.gemm_fused import ops as gemm_ops
from repro.kernels.gemm_fused import ref as gemm_ref
from repro.kernels.rmsnorm import ops as rms_ops
from repro.kernels.rmsnorm import ref as rms_ref
from repro.kernels.ssd import ops as ssd_ops
from repro.kernels.ssd import ref as ssd_ref

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == np.float16 or dtype == jnp.bfloat16 \
        else dict(rtol=1e-4, atol=1e-4)


class TestGemmFused:
    @pytest.mark.parametrize("m,n,k", [(32, 32, 32), (64, 128, 96),
                                       (128, 64, 256), (8, 8, 8)])
    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_shapes_dtypes(self, m, n, k, dtype):
        x = RNG.standard_normal((m, k)).astype(dtype)
        w = RNG.standard_normal((k, n)).astype(dtype)
        got = np.asarray(gemm_ops.gemm_leaky_relu(x, w), np.float32)
        want = np.asarray(gemm_ref.gemm_leaky_relu(x, w), np.float32)
        np.testing.assert_allclose(got, want, **_tol(dtype))

    @pytest.mark.parametrize("bm,bn,bk", [(16, 16, 16), (32, 16, 8), (8, 32, 32)])
    def test_knob_grid(self, bm, bn, bk):
        m, n, k = 64, 64, 64
        x = RNG.standard_normal((m, k)).astype(np.float32)
        w = RNG.standard_normal((k, n)).astype(np.float32)
        sched = Schedule(knobs={"bm": bm, "bn": bn, "bk": bk})
        fn = gemm_ops.build(sched, m=m, n=n, k=k)
        np.testing.assert_allclose(np.asarray(fn(x, w)),
                                   np.asarray(gemm_ref.gemm_leaky_relu(x, w)),
                                   rtol=1e-4, atol=1e-4)

    def test_all_single_moves_preserve_semantics(self):
        """Every legal paper-action applied to the default order must leave
        the kernel's output bit-identical on the same inputs."""
        m = n = k = 32
        x = RNG.standard_normal((m, k)).astype(np.float32)
        w = RNG.standard_normal((k, n)).astype(np.float32)
        sched = Schedule(knobs={"bm": 16, "bn": 16, "bk": 8})
        program = gemm_ops.program_for(sched, m=m, n=n, k=k)
        base = np.asarray(gemm_ops.build(sched, m=m, n=n, k=k)(x, w))
        order = program.default_order()
        for idx, d in program.legal_moves(order):
            new = program.move(order, idx, d)
            fn = gemm_ops.build(sched.with_order(new), m=m, n=n, k=k)
            np.testing.assert_array_equal(np.asarray(fn(x, w)), base)

    def test_prefetched_schedule_matches(self):
        """The fully software-pipelined schedule (all loads hoisted) is legal
        and numerically identical — the schedule SIP converges to."""
        m = n = 32; k = 64
        sched = Schedule(knobs={"bm": 16, "bn": 16, "bk": 16})
        program = gemm_ops.program_for(sched, m=m, n=n, k=k)
        loads = [i for i in program.mem_indices()
                 if not program.instrs[i].is_store]
        rest = [i for i in range(len(program)) if i not in loads]
        # init_acc first, then all loads, then compute chain
        order = tuple([rest[0]] + loads + rest[1:])
        assert program.is_legal(order)
        x = RNG.standard_normal((m, k)).astype(np.float32)
        w = RNG.standard_normal((k, n)).astype(np.float32)
        fn = gemm_ops.build(sched.with_order(order), m=m, n=n, k=k)
        want = gemm_ops.build(sched, m=m, n=n, k=k)(x, w)
        np.testing.assert_array_equal(np.asarray(fn(x, w)), np.asarray(want))


class TestFlashAttention:
    def _mk(self, b, hq, hkv, sq, skv, d, dtype=np.float32):
        q = RNG.standard_normal((b, hq, sq, d)).astype(dtype)
        k = RNG.standard_normal((b, hkv, skv, d)).astype(dtype)
        v = RNG.standard_normal((b, hkv, skv, d)).astype(dtype)
        return q, k, v

    @pytest.mark.parametrize("b,hq,hkv,s,d", [
        (1, 1, 1, 32, 16), (2, 4, 2, 64, 16), (1, 8, 1, 128, 32),
        (2, 2, 2, 64, 64), (1, 2, 1, 37, 16)])
    def test_causal_gqa_shapes(self, b, hq, hkv, s, d):
        q, k, v = self._mk(b, hq, hkv, s, s, d)
        got = np.asarray(fa_ops.flash_attention(q, k, v))
        want = np.asarray(fa_ref.attention(q, k, v, causal=True))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        q, k, v = self._mk(1, 2, 1, 64, 64, 16, dtype)
        got = np.asarray(fa_ops.flash_attention(q, k, v), np.float32)
        want = np.asarray(fa_ref.attention(q, k, v, causal=True), np.float32)
        np.testing.assert_allclose(got, want, **_tol(dtype))

    def test_bidirectional(self):
        q, k, v = self._mk(1, 2, 2, 64, 64, 16)
        got = np.asarray(fa_ops.flash_attention_bidir(q, k, v))
        want = np.asarray(fa_ref.attention(q, k, v, causal=False))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("window", [8, 24, 64])
    def test_sliding_window(self, window):
        q, k, v = self._mk(1, 2, 1, 64, 64, 16)
        swa = fa_ops.make(causal=True, window=window)
        got = np.asarray(swa(q, k, v))
        want = np.asarray(fa_ref.attention(q, k, v, causal=True, window=window))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_decode_right_aligned(self):
        q, k, v = self._mk(2, 4, 2, 64, 64, 16)
        q1 = q[:, :, :1]
        got = np.asarray(fa_ops.flash_attention(q1, k, v))
        want = np.asarray(fa_ref.attention(q1, k, v, causal=True))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("n_chunks", [1, 2, 4])
    def test_kv_chunking_knob(self, n_chunks):
        q, k, v = self._mk(1, 2, 1, 64, 64, 16)
        static = dict(b=1, hq=2, hkv=1, sq=64, skv=64, d=16, causal=True,
                      window=None, dtype="float32")
        sched = Schedule(knobs={"bq": 32, "bk": 32, "n_chunks": n_chunks})
        fn = fa_ops.build(sched, **static)
        got = np.asarray(fn(q, k, v))
        want = np.asarray(fa_ref.attention(q, k, v, causal=True))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("sq,dtype,bq", [
        (37, "bfloat16", (37,)), (48, "bfloat16", (16,)),
        (48, "float32", (16, 8)), (8, "bfloat16", (8,)),
        (512, "bfloat16", (256, 512, 128, 64, 32, 16))])
    def test_space_offers_only_lowerable_tiles(self, sq, dtype, bq):
        """Second-minor tiles are multiples of the dtype's sublane tiling
        (8 rows of f32, 16 of bf16) that divide the length, or else the
        whole length — never a 1-row tile the TPU lowering refuses."""
        sp = fa_ops.space(b=1, hq=2, hkv=1, sq=sq, skv=sq, d=128,
                          causal=True, window=None, dtype=dtype)
        assert sp.knob("bq").choices == bq
        assert sp.knob("bk").choices == bq
        sub = 16 if dtype == "bfloat16" else 8
        for n in sp.knob("n_chunks").choices:
            ck = sp.knob("bk").choices[0] // n
            assert n == 1 or ck % sub == 0

    def test_all_single_moves_preserve_semantics(self):
        q, k, v = self._mk(1, 2, 1, 32, 32, 16)
        static = dict(b=1, hq=2, hkv=1, sq=32, skv=32, d=16, causal=True,
                      window=None, dtype="float32")
        sched = Schedule(knobs={"bq": 16, "bk": 16, "n_chunks": 2})
        program = fa_ops.program_for(sched, **static)
        base = np.asarray(fa_ops.build(sched, **static)(q, k, v))
        order = program.default_order()
        moves = program.legal_moves(order)
        assert moves, "attention body must expose movable mem instructions"
        for idx, d in moves:
            new = program.move(order, idx, d)
            fn = fa_ops.build(sched.with_order(new), **static)
            np.testing.assert_array_equal(np.asarray(fn(q, k, v)), base)


class TestRmsnorm:
    @pytest.mark.parametrize("rows,d", [(8, 64), (32, 128), (64, 32)])
    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
    def test_shapes_dtypes(self, rows, d, dtype):
        x = RNG.standard_normal((rows, d)).astype(dtype)
        g = RNG.standard_normal((d,)).astype(dtype)
        got = np.asarray(rms_ops.rmsnorm(x, g), np.float32)
        want = np.asarray(rms_ref.rmsnorm(x, g), np.float32)
        np.testing.assert_allclose(got, want, **_tol(dtype))

    @pytest.mark.parametrize("n_chunks", [1, 2, 4])
    def test_chunking(self, n_chunks):
        x = RNG.standard_normal((16, 64)).astype(np.float32)
        g = RNG.standard_normal((64,)).astype(np.float32)
        sched = Schedule(knobs={"br": 8, "n_chunks": n_chunks})
        fn = rms_ops.build(sched, rows=16, d=64, dtype="float32")
        np.testing.assert_allclose(np.asarray(fn(x, g)),
                                   np.asarray(rms_ref.rmsnorm(x, g)),
                                   rtol=1e-4, atol=1e-4)


class TestSSD:
    def _mk(self, bt=2, s=64, h=4, p=8, n=16):
        x = RNG.standard_normal((bt, s, h, p)).astype(np.float32)
        dt = (np.abs(RNG.standard_normal((bt, s, h))) * 0.1 + 0.01).astype(np.float32)
        A = -np.abs(RNG.standard_normal(h)).astype(np.float32)
        B = (RNG.standard_normal((bt, s, n)) * 0.3).astype(np.float32)
        C = (RNG.standard_normal((bt, s, n)) * 0.3).astype(np.float32)
        D = RNG.standard_normal(h).astype(np.float32)
        return x, dt, A, B, C, D

    @pytest.mark.parametrize("chunk", [16, 32, 64])
    def test_chunked_matches_naive(self, chunk):
        args = self._mk()
        got = np.asarray(ssd_ops.ssd_chunked(*args, chunk=chunk))
        want = np.asarray(ssd_ref.ssd(*args))
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)

    def test_decode_step_parity(self):
        x, dt, A, B, C, D = self._mk(s=64)
        y_full, st_full = ssd_ops.ssd_chunked(x, dt, A, B, C, D, chunk=16,
                                              return_state=True)
        _, st = ssd_ops.ssd_chunked(x[:, :48], dt[:, :48], A, B[:, :48],
                                    C[:, :48], D, chunk=16, return_state=True)
        outs = []
        for t in range(48, 64):
            st, y = ssd_ops.ssd_step(st, x[:, t], dt[:, t], A, B[:, t],
                                     C[:, t], D)
            outs.append(np.asarray(y))
        np.testing.assert_allclose(np.stack(outs, 1), np.asarray(y_full[:, 48:]),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(st), np.asarray(st_full),
                                   rtol=1e-4, atol=1e-4)

    def test_init_state_continuation(self):
        x, dt, A, B, C, D = self._mk(s=64)
        y_full = np.asarray(ssd_ops.ssd_chunked(x, dt, A, B, C, D, chunk=16))
        _, st = ssd_ops.ssd_chunked(x[:, :32], dt[:, :32], A, B[:, :32],
                                    C[:, :32], D, chunk=16, return_state=True)
        y_tail = np.asarray(ssd_ops.ssd_chunked(
            x[:, 32:], dt[:, 32:], A, B[:, 32:], C[:, 32:], D, chunk=16,
            init_state=st))
        np.testing.assert_allclose(y_tail, y_full[:, 32:], rtol=1e-4, atol=1e-4)


def test_importing_repro_touches_no_backend():
    """Every module under ``repro`` imports without initializing a JAX
    backend: a kernel asks whether to interpret when it is called."""
    code = ("import importlib, pathlib, repro\n"
            "for root in repro.__path__:\n"
            "    for f in sorted(pathlib.Path(root).rglob('*.py')):\n"
            "        rel = f.relative_to(pathlib.Path(root).parent)\n"
            "        importlib.import_module('.'.join(rel.with_suffix('')"
            ".parts).removesuffix('.__init__'))\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, sorted(xla_bridge._backends)\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
