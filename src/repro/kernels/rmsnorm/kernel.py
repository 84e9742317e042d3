"""Schedule-parameterized Pallas RMSNorm — a memory-bound SIP target.

Rows are tiled over a 1-D parallel grid; the feature dimension is processed
in ``n_chunks`` pieces so the body contains several independent MEM loads
(x chunks + the gamma chunks) whose placement SIP can permute against the
square/accumulate compute.  For a bandwidth-bound kernel the win comes from
issuing every load before the reduction chain — which is exactly what the
annealer converges to (see benchmarks/table3_gemm.py's rmsnorm sibling).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ir import Instr, Kind, Program
from repro.kernels import interpret_mode

EPS = 1e-6


def make_program(*, br: int, d: int, n_chunks: int, dtype=jnp.float32,
                 rows: int = 0) -> Program:
    assert d % n_chunks == 0
    replications = max(rows // br, 1) if rows else 1
    cd = d // n_chunks
    esize = jnp.dtype(dtype).itemsize
    instrs: list[Instr] = []

    def ld_x(env, c):
        return {f"x{c}": env["x_ref"][:, pl.ds(c * cd, cd)].astype(jnp.float32)}

    def ld_g(env, c):
        return {f"g{c}": env["g_ref"][0, pl.ds(c * cd, cd)].astype(jnp.float32)}

    def sq(env, c):
        x = env[f"x{c}"]
        return {f"ss{c}": jnp.sum(x * x, axis=1, keepdims=True)}

    for c in range(n_chunks):
        instrs.append(Instr(name=f"ld_x{c}", kind=Kind.MEM, inputs=(),
                            outputs=(f"x{c}",), fn=functools.partial(ld_x, c=c),
                            buffer="x", bytes=br * cd * esize))
        instrs.append(Instr(name=f"sq{c}", kind=Kind.COMPUTE, inputs=(f"x{c}",),
                            outputs=(f"ss{c}",), fn=functools.partial(sq, c=c),
                            flops=2 * br * cd))

    def rstd(env):
        tot = env["ss0"]
        for c in range(1, n_chunks):
            tot = tot + env[f"ss{c}"]
        return {"rstd": jax.lax.rsqrt(tot / d + EPS)}

    instrs.append(Instr(name="rstd", kind=Kind.COMPUTE,
                        inputs=tuple(f"ss{c}" for c in range(n_chunks)),
                        outputs=("rstd",), fn=rstd, flops=2 * br))

    def scale(env, c):
        return {f"y{c}": (env[f"x{c}"] * env["rstd"] * env[f"g{c}"])}

    def st_y(env, c):
        env["o_ref"][:, pl.ds(c * cd, cd)] = env[f"y{c}"].astype(dtype)
        return {}

    for c in range(n_chunks):
        instrs.append(Instr(name=f"ld_g{c}", kind=Kind.MEM, inputs=(),
                            outputs=(f"g{c}",), fn=functools.partial(ld_g, c=c),
                            buffer="g", bytes=cd * esize))
        instrs.append(Instr(name=f"scale{c}", kind=Kind.COMPUTE,
                            inputs=(f"x{c}", "rstd", f"g{c}"),
                            outputs=(f"y{c}",), fn=functools.partial(scale, c=c),
                            flops=2 * br * cd))
        instrs.append(Instr(name=f"st_y{c}", kind=Kind.MEM, inputs=(f"y{c}",),
                            outputs=(), fn=functools.partial(st_y, c=c),
                            buffer="o", is_store=True, bytes=br * cd * esize))
    return Program(instrs, replications=replications)


def pallas_rmsnorm(x: jax.Array, gamma: jax.Array, *, br: int,
                   n_chunks: int = 1, order=None,
                   interpret: bool | None = None) -> jax.Array:
    if interpret is None:
        interpret = interpret_mode()
    rows, d = x.shape
    assert rows % br == 0 and gamma.shape == (d,)
    program = make_program(br=br, d=d, n_chunks=n_chunks, dtype=x.dtype)

    def kernel(x_ref, g_ref, o_ref):
        program.execute({"x_ref": x_ref, "g_ref": g_ref, "o_ref": o_ref}, order)

    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        grid=(rows // br,),
        in_specs=[pl.BlockSpec((br, d), lambda i: (i, 0)),
                  pl.BlockSpec((1, d), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        interpret=interpret,
        **kwargs,
    )(x, gamma[None, :])
