"""Schedule-parameterized Pallas kernel: fused GEMM + LeakyReLU.

The kernel body is *emitted* from a :class:`~repro.core.ir.Program`: the K
dimension is processed in ``bk``-sized steps inside the body, each step
contributing two MEM loads (an x-tile and a w-tile — the analogue of the
paper's LDGSTS global-memory instructions) and one MXU dot (COMPUTE).  The
default order interleaves ``ld_x, ld_w, dot`` per step, which is what a
straightforward compiler emits (cf. Listing 4); SIP's annealer reorders the
loads ahead of the dots (software pipelining / latency hiding, cf. Listing 5).

Grid: ``(M/bm, N/bn)`` with both dimensions parallel; the accumulator lives in
registers/VREGs as a traced value, accumulated in fp32, with the LeakyReLU
epilogue fused before the single store.

VMEM working set per program: ``bm*K + K*bn + bm*bn`` elements — the knob
choices keep this under the v5e VMEM budget for the benchmarked shapes.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ir import Instr, Kind, Program
from repro.kernels import interpret_mode

ALPHA = 0.01


def make_program(*, m: int, n: int, k: int, bm: int, bn: int, bk: int,
                 dtype=jnp.float32) -> Program:
    """Build the instruction stream for one (bm x bn) output tile."""
    esize = jnp.dtype(dtype).itemsize
    k_steps = math.ceil(k / bk)
    instrs: list[Instr] = []

    def ld_x(env, s=0, bk=bk):
        return {f"x{s}": env["x_ref"][:, pl.ds(s * bk, bk)]}

    def ld_w(env, s=0, bk=bk):
        return {f"w{s}": env["w_ref"][pl.ds(s * bk, bk), :]}

    def dot(env, s=0):
        part = jnp.dot(env[f"x{s}"], env[f"w{s}"],
                       preferred_element_type=jnp.float32)
        return {f"acc{s + 1}": env[f"acc{s}"] + part}

    instrs.append(Instr(name="init_acc", kind=Kind.COMPUTE, inputs=(),
                        outputs=("acc0",),
                        fn=lambda env: {"acc0": jnp.zeros((bm, bn), jnp.float32)},
                        flops=0))
    for s in range(k_steps):
        instrs.append(Instr(name=f"ld_x{s}", kind=Kind.MEM, inputs=(),
                            outputs=(f"x{s}",), fn=functools.partial(ld_x, s=s),
                            buffer="x", bytes=bm * bk * esize))
        instrs.append(Instr(name=f"ld_w{s}", kind=Kind.MEM, inputs=(),
                            outputs=(f"w{s}",), fn=functools.partial(ld_w, s=s),
                            buffer="w", bytes=bk * bn * esize))
        instrs.append(Instr(name=f"dot{s}", kind=Kind.COMPUTE,
                            inputs=(f"x{s}", f"w{s}", f"acc{s}"),
                            outputs=(f"acc{s + 1}",),
                            fn=functools.partial(dot, s=s),
                            flops=2 * bm * bn * bk))
    acc_final = f"acc{k_steps}"

    def epilogue(env):
        y = env[acc_final]
        return {"y": jnp.where(y >= 0, y, ALPHA * y).astype(dtype)}

    instrs.append(Instr(name="leaky_relu", kind=Kind.COMPUTE,
                        inputs=(acc_final,), outputs=("y",), fn=epilogue,
                        flops=bm * bn))

    def store(env):
        env["o_ref"][...] = env["y"]
        return {}

    instrs.append(Instr(name="st_o", kind=Kind.MEM, inputs=("y",), outputs=(),
                        fn=store, buffer="o", is_store=True,
                        bytes=bm * bn * esize))
    return Program(instrs, replications=(m // bm) * (n // bn))


def pallas_gemm_leaky_relu(x: jax.Array, w: jax.Array, *, bm: int, bn: int,
                           bk: int, order=None,
                           interpret: bool | None = None) -> jax.Array:
    if interpret is None:
        interpret = interpret_mode()
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    program = make_program(m=m, n=n, k=k, bm=bm, bn=bn, bk=bk, dtype=x.dtype)

    def kernel(x_ref, w_ref, o_ref):
        program.execute({"x_ref": x_ref, "w_ref": w_ref, "o_ref": o_ref}, order)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        grid=(m // bm, n // bn),
        in_specs=[pl.BlockSpec((bm, k), lambda i, j: (i, 0)),
                  pl.BlockSpec((k, bn), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=interpret,
        **kwargs,
    )(x, w)
