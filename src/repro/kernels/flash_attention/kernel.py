"""Schedule-parameterized Pallas flash-attention (fwd), TPU-tiled.

Online-softmax attention with the kv sequence swept by the innermost
(sequential) grid dimension and running (m, l, acc) statistics carried in
VMEM scratch — the standard TPU flash-attention structure.  As with the GEMM
kernel, the body is emitted from a :class:`~repro.core.ir.Program`:

* MEM instructions: the q-tile load, per-chunk K loads, per-chunk V loads,
  the output store.  These are SIP's movable set — the analogue of the
  LDGSTS instructions the paper reorders (Listings 4/5).  In particular the
  V loads have no dependency on the softmax chain, so the annealer can hoist
  them next to the K loads (overlapping the V transfer with QK^T + softmax),
  which is exactly the latency-hiding schedule hand-tuned in prior work.
* COMPUTE instructions: QK^T dots (MXU), masking, the online-softmax update,
  PV dots, the scratch read/update (VPU).

GQA is handled in the K/V BlockSpec index maps (query head -> kv head), so
no materialized head broadcast is needed.  Causal and sliding-window masks
are applied in-body from global row/col indices; fully-masked blocks are
numerically safe (finite NEG_INF + explicit re-masking of p).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ir import Instr, Kind, Program
from repro.kernels import interpret_mode

NEG_INF = -1e30


def make_program(*, bq: int, bk: int, n_chunks: int, d: int, sq: int, skv: int,
                 causal: bool, window: int | None, dtype=jnp.float32,
                 batch_heads: int = 1) -> Program:
    assert bk % n_chunks == 0
    ck = bk // n_chunks
    replications = batch_heads * (sq // bq) * (skv // bk)
    esize = jnp.dtype(dtype).itemsize
    scale = d ** -0.5
    instrs: list[Instr] = []

    # ---- loads -------------------------------------------------------------
    instrs.append(Instr(
        name="ld_q", kind=Kind.MEM, inputs=(), outputs=("q",),
        fn=lambda env: {"q": env["q_ref"][0].astype(jnp.float32)},
        buffer="q", bytes=bq * d * esize))

    def ld_k(env, c):
        return {f"k{c}": env["k_ref"][0, pl.ds(c * ck, ck), :].astype(jnp.float32)}

    def ld_v(env, c):
        return {f"v{c}": env["v_ref"][0, pl.ds(c * ck, ck), :].astype(jnp.float32)}

    def qk(env, c):
        s = jax.lax.dot_general(env["q"], env[f"k{c}"],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        return {f"s{c}": s}

    def mk_mask(env, c):
        i, j = env["i"], env["j"]
        rows = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, ck), 0) + (skv - sq)
        cols = j * bk + c * ck + jax.lax.broadcasted_iota(jnp.int32, (bq, ck), 1)
        m = jnp.ones((bq, ck), dtype=bool)
        if causal:
            m &= cols <= rows
        if window is not None:
            m &= cols > rows - window
        return {f"mask{c}": m,
                f"sm{c}": jnp.where(m, env[f"s{c}"], NEG_INF)}

    for c in range(n_chunks):
        instrs.append(Instr(name=f"ld_k{c}", kind=Kind.MEM, inputs=(),
                            outputs=(f"k{c}",), fn=functools.partial(ld_k, c=c),
                            buffer="k", bytes=ck * d * esize))
        instrs.append(Instr(name=f"qk{c}", kind=Kind.COMPUTE,
                            inputs=("q", f"k{c}"), outputs=(f"s{c}",),
                            fn=functools.partial(qk, c=c),
                            flops=2 * bq * ck * d))
        instrs.append(Instr(name=f"mask{c}", kind=Kind.COMPUTE,
                            inputs=(f"s{c}",), outputs=(f"sm{c}", f"mask{c}"),
                            fn=functools.partial(mk_mask, c=c),
                            flops=bq * ck))

    # ---- read running stats (VMEM scratch; init on first kv block) ----------
    def ld_stats(env):
        j = env["j"]
        first = j == 0
        m_prev = jnp.where(first, jnp.full((bq, 1), NEG_INF, jnp.float32),
                           env["m_ref"][...])
        l_prev = jnp.where(first, jnp.zeros((bq, 1), jnp.float32),
                           env["l_ref"][...])
        acc_prev = jnp.where(first, jnp.zeros((bq, d), jnp.float32),
                             env["acc_ref"][...])
        return {"m_prev": m_prev, "l_prev": l_prev, "acc_prev": acc_prev}

    instrs.append(Instr(name="ld_stats", kind=Kind.COMPUTE, inputs=(),
                        outputs=("m_prev", "l_prev", "acc_prev"),
                        fn=ld_stats, buffer="stats", flops=0))

    # ---- online softmax ------------------------------------------------------
    def softmax_update(env):
        m_cur = env["m_prev"]
        for c in range(n_chunks):
            m_cur = jnp.maximum(m_cur, jnp.max(env[f"sm{c}"], axis=1, keepdims=True))
        corr = jnp.exp(env["m_prev"] - m_cur)
        l_new = corr * env["l_prev"]
        out = {"m_new": m_cur, "corr": corr}
        for c in range(n_chunks):
            p = jnp.exp(env[f"sm{c}"] - m_cur) * env[f"mask{c}"]
            out[f"p{c}"] = p
            l_new = l_new + jnp.sum(p, axis=1, keepdims=True)
        out["l_new"] = l_new
        return out

    instrs.append(Instr(
        name="softmax", kind=Kind.COMPUTE,
        inputs=("m_prev", "l_prev") + tuple(f"sm{c}" for c in range(n_chunks))
               + tuple(f"mask{c}" for c in range(n_chunks)),
        outputs=("m_new", "l_new", "corr") + tuple(f"p{c}" for c in range(n_chunks)),
        fn=softmax_update, flops=6 * bq * bk))

    # ---- PV and accumulator ---------------------------------------------------
    def pv(env, c):
        return {f"pv{c}": jnp.dot(env[f"p{c}"], env[f"v{c}"],
                                  preferred_element_type=jnp.float32)}

    for c in range(n_chunks):
        instrs.append(Instr(name=f"ld_v{c}", kind=Kind.MEM, inputs=(),
                            outputs=(f"v{c}",), fn=functools.partial(ld_v, c=c),
                            buffer="v", bytes=ck * d * esize))
        instrs.append(Instr(name=f"pv{c}", kind=Kind.COMPUTE,
                            inputs=(f"p{c}", f"v{c}"), outputs=(f"pv{c}",),
                            fn=functools.partial(pv, c=c),
                            flops=2 * bq * ck * d))

    def accumulate(env):
        acc = env["corr"] * env["acc_prev"]
        for c in range(n_chunks):
            acc = acc + env[f"pv{c}"]
        return {"acc_new": acc}

    instrs.append(Instr(
        name="accum", kind=Kind.COMPUTE,
        inputs=("corr", "acc_prev") + tuple(f"pv{c}" for c in range(n_chunks)),
        outputs=("acc_new",), fn=accumulate, flops=2 * bq * d * n_chunks))

    # ---- write-back -----------------------------------------------------------
    def st_stats(env):
        env["m_ref"][...] = env["m_new"]
        env["l_ref"][...] = env["l_new"]
        env["acc_ref"][...] = env["acc_new"]
        return {}

    instrs.append(Instr(name="st_stats", kind=Kind.COMPUTE,
                        inputs=("m_new", "l_new", "acc_new"), outputs=(),
                        fn=st_stats, buffer="stats", is_store=True, flops=0))

    def st_o(env):
        @pl.when(env["j"] == env["nkv"] - 1)
        def _():
            l_safe = jnp.maximum(env["l_new"], 1e-30)
            env["o_ref"][0] = (env["acc_new"] / l_safe).astype(dtype)
        return {}

    instrs.append(Instr(name="st_o", kind=Kind.MEM,
                        inputs=("acc_new", "l_new"), outputs=(),
                        fn=st_o, buffer="o", is_store=True,
                        bytes=bq * d * esize))
    return Program(instrs, replications=replications)


def pallas_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                     bq: int, bk: int, n_chunks: int = 1,
                     causal: bool = True, window: int | None = None,
                     order=None, interpret: bool | None = None) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D) -> (B, Hq, Sq, D)."""
    if interpret is None:
        interpret = interpret_mode()
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0 and sq % bq == 0 and skv % bk == 0
    group = hq // hkv
    program = make_program(bq=bq, bk=bk, n_chunks=n_chunks, d=d, sq=sq,
                           skv=skv, causal=causal, window=window,
                           dtype=q.dtype)

    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)

    def kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
        env = {"q_ref": q_ref, "k_ref": k_ref, "v_ref": v_ref, "o_ref": o_ref,
               "m_ref": m_ref, "l_ref": l_ref, "acc_ref": acc_ref,
               "i": pl.program_id(1), "j": pl.program_id(2),
               "nkv": pl.num_programs(2)}
        program.execute(env, order)

    def kv_index(bh, i, j):
        return ((bh // hq) * hkv + (bh % hq) // group, j, 0)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        grid=(b * hq, sq // bq, skv // bk),
        in_specs=[pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
                  pl.BlockSpec((1, bk, d), kv_index),
                  pl.BlockSpec((1, bk, d), kv_index)],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, i, j: (bh, i, 0)),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        **kwargs,
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, d)
