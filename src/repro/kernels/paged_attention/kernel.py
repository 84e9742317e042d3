"""Schedule-parameterized Pallas paged-KV gather.

``out[b, i] = store[page_table[b, i]]`` — the cache-read indirection that
paged serving memory puts on the decode hot path.  A page is (ps, H, D)
(per-head keys or values) or (ps, D) (a latent cache, one vector a
token).  One grid step copies one page; the page table rides in
scalar-prefetch memory (SMEM), so the *input* BlockSpec's index map is
data-dependent — each step's DMA source block is steered by
``pt_ref[b, i]`` at page granularity, the Pallas analogue of the page-table
walk a paged-attention CUDA kernel does per block.

The body is emitted from a :class:`~repro.core.ir.Program` whose
instructions are pure MEM traffic: the page is tiled into (row-block x
d-chunk) pieces, each moved by a load/store pair.  That tile set is SIP's
movable set — the stochastic search reorders the copy stream (e.g.
interleaving loads of tile ``i+1`` with the store of tile ``i``), the same
LDGSTS-style latency hiding the paper perturbs in SASS.  There is no
compute chain; the schedule family is all memory-level parallelism.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.ir import Instr, Kind, Program
from repro.kernels import interpret_mode


def make_program(*, ps: int, h: int, d: int, rows: int, n_chunks: int,
                 dtype=jnp.float32, total_pages: int = 1) -> Program:
    """The per-grid-step copy program: ``rows`` row-blocks x ``n_chunks``
    d-chunks, one (load, store) MEM pair per tile."""
    assert ps % rows == 0 and d % n_chunks == 0
    rb, cd = ps // rows, d // n_chunks
    esize = jnp.dtype(dtype).itemsize
    instrs: list[Instr] = []

    def ld(env, r, c):
        tile = env["store_ref"][0, pl.ds(r * rb, rb), ..., pl.ds(c * cd, cd)]
        return {f"t{r}_{c}": tile}

    def st(env, r, c):
        env["out_ref"][0, 0, pl.ds(r * rb, rb), ..., pl.ds(c * cd, cd)] = \
            env[f"t{r}_{c}"]
        return {}

    for r in range(rows):
        for c in range(n_chunks):
            nbytes = rb * h * cd * esize
            instrs.append(Instr(
                name=f"ld_r{r}c{c}", kind=Kind.MEM, inputs=(),
                outputs=(f"t{r}_{c}",), fn=functools.partial(ld, r=r, c=c),
                buffer="store", bytes=nbytes))
            instrs.append(Instr(
                name=f"st_r{r}c{c}", kind=Kind.MEM, inputs=(f"t{r}_{c}",),
                outputs=(), fn=functools.partial(st, r=r, c=c),
                buffer="out", is_store=True, bytes=nbytes))
    return Program(instrs, replications=total_pages)


def paged_gather(store: jax.Array, page_table: jax.Array, *,
                 rows: int, n_chunks: int, order=None,
                 interpret: bool | None = None) -> jax.Array:
    """store: (P, ps, [H,] D); page_table: (B, n) int32 -> (B, n, ps, [H,]
    D)."""
    if interpret is None:
        interpret = interpret_mode()
    p, ps, *mid, d = store.shape
    b, n = page_table.shape
    program = make_program(ps=ps, h=mid[0] if mid else 1, d=d, rows=rows,
                           n_chunks=n_chunks, dtype=store.dtype,
                           total_pages=b * n)
    rest = (0,) * (len(mid) + 1)

    def kernel(pt_ref, store_ref, out_ref):
        del pt_ref      # consumed by the BlockSpec index maps
        env = {"store_ref": store_ref, "out_ref": out_ref}
        program.execute(env, order)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, n),
        in_specs=[pl.BlockSpec((1, ps, *mid, d),
                               lambda bi, i, pt_ref: (pt_ref[bi, i], 0, *rest))],
        out_specs=pl.BlockSpec((1, 1, ps, *mid, d),
                               lambda bi, i, pt_ref: (bi, i, 0, *rest)),
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, n, ps, *mid, d), store.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
    )(page_table.astype(jnp.int32), store)
