"""Schedule-parameterized Pallas kernel for the SSD intra-chunk block.

The Mamba-2 chunked algorithm (ops.ssd_chunked) splits into an intra-chunk
quadratic part — for each (sequence-chunk, head): ``y = (C B^T ⊙ L) x`` with
L the cumulative-decay lower-triangular matrix — and a cheap inter-chunk
recurrence.  The quadratic part is the compute hot spot and maps cleanly to
one MXU-friendly Pallas body per (batch·chunk, head) grid cell.

As with the other kernels the body is emitted from a
:class:`~repro.core.ir.Program`: four MEM loads (C, B, decay, x) whose
placement SIP permutes against the two MXU dots and the VPU decay math.
This kernel has NO macro knobs (the chunk length is fixed by the caller) —
it exercises the paper-faithful, order-only search space.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ir import Instr, Kind, Program
from repro.kernels import interpret_mode


def make_program(*, q: int, n: int, p: int, dtype=jnp.float32,
                 grid: int = 1) -> Program:
    esize = jnp.dtype(dtype).itemsize
    instrs: list[Instr] = []

    instrs.append(Instr(name="ld_c", kind=Kind.MEM, inputs=(), outputs=("c",),
                        fn=lambda env: {"c": env["c_ref"][0].astype(jnp.float32)},
                        buffer="c", bytes=q * n * esize))
    instrs.append(Instr(name="ld_b", kind=Kind.MEM, inputs=(), outputs=("b",),
                        fn=lambda env: {"b": env["b_ref"][0].astype(jnp.float32)},
                        buffer="b", bytes=q * n * esize))
    instrs.append(Instr(name="ld_la", kind=Kind.MEM, inputs=(), outputs=("la",),
                        fn=lambda env: {"la": env["la_ref"][0, 0].astype(jnp.float32)},
                        buffer="la", bytes=q * esize))
    instrs.append(Instr(name="ld_x", kind=Kind.MEM, inputs=(), outputs=("x",),
                        fn=lambda env: {"x": env["x_ref"][0, :, 0].astype(jnp.float32)},
                        buffer="x", bytes=q * p * esize))

    instrs.append(Instr(
        name="dot_cb", kind=Kind.COMPUTE, inputs=("c", "b"), outputs=("s",),
        fn=lambda env: {"s": jax.lax.dot_general(
            env["c"], env["b"], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)},
        flops=2 * q * q * n))

    def decay(env):
        cum = jnp.cumsum(env["la"], axis=0)               # (Q, 1)
        diff = cum - cum[:, 0][None, :]                    # (Q, Q) i,j
        mask = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >=
                jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
        return {"L": jnp.where(mask, jnp.exp(jnp.where(mask, diff, 0.0)), 0.0)}

    instrs.append(Instr(name="decay", kind=Kind.COMPUTE, inputs=("la",),
                        outputs=("L",), fn=decay, flops=4 * q * q))
    instrs.append(Instr(name="mask_mul", kind=Kind.COMPUTE, inputs=("s", "L"),
                        outputs=("w",),
                        fn=lambda env: {"w": env["s"] * env["L"]},
                        flops=q * q))
    instrs.append(Instr(
        name="dot_y", kind=Kind.COMPUTE, inputs=("w", "x"), outputs=("y",),
        fn=lambda env: {"y": jnp.dot(env["w"], env["x"],
                                     preferred_element_type=jnp.float32)},
        flops=2 * q * q * p))

    def store(env):
        env["o_ref"][0, :, 0] = env["y"].astype(dtype)
        return {}

    instrs.append(Instr(name="st_y", kind=Kind.MEM, inputs=("y",), outputs=(),
                        fn=store, buffer="o", is_store=True,
                        bytes=q * p * esize))
    return Program(instrs, replications=grid)


def pallas_ssd_intra(xb: jax.Array, la: jax.Array, B: jax.Array,
                     C: jax.Array, *, order=None,
                     interpret: bool | None = None) -> jax.Array:
    """Intra-chunk SSD.  xb: (G, Q, H, P) dt-weighted inputs; la: (G, Q, H)
    log-decays; B, C: (G, Q, N).  G = batch*chunks.  Returns (G, Q, H, P)."""
    if interpret is None:
        interpret = interpret_mode()
    g, q, h, p = xb.shape
    n = B.shape[-1]
    program = make_program(q=q, n=n, p=p, dtype=xb.dtype)

    def kernel(c_ref, b_ref, la_ref, x_ref, o_ref):
        program.execute({"c_ref": c_ref, "b_ref": b_ref, "la_ref": la_ref,
                         "x_ref": x_ref, "o_ref": o_ref}, order)

    la3 = jnp.moveaxis(la, -1, 1)[..., None]      # (G, H, Q, 1)
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((g, q, h, p), xb.dtype),
        grid=(g, h),
        in_specs=[pl.BlockSpec((1, q, n), lambda i, j: (i, 0, 0)),
                  pl.BlockSpec((1, q, n), lambda i, j: (i, 0, 0)),
                  pl.BlockSpec((1, 1, q, 1), lambda i, j: (i, j, 0, 0)),
                  pl.BlockSpec((1, q, 1, p), lambda i, j: (i, 0, j, 0))],
        out_specs=pl.BlockSpec((1, q, 1, p), lambda i, j: (i, 0, j, 0)),
        interpret=interpret,
        **kwargs,
    )(C, B, la3, xb)
    return out
