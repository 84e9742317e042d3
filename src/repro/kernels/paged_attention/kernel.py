"""Schedule-parameterized Pallas paged-KV reads: the gather, and the
fused decode read (:func:`paged_decode_attention`, at the end).

``out[b, i] = store[page_table[b, i]]`` — the cache-read indirection that
paged serving memory puts in front of a chunk's attention.  A page is
(ps, H, D) (per-head keys or values) or (ps, D) (a latent cache, one
vector a token).  One grid step copies one page; the page table rides in
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
import math

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


# ------------------------------------------------- fused paged decode read
NEG_INF = -1e30


def decode_program(*, pages_per_block: int, page_bytes: int, shared_kv: bool,
                   hg: int, cols: int, d: int, dv: int,
                   total_blocks: int = 1) -> Program:
    """One block of the fused decode read: start the next block's page
    copies, wait for this block's, attend.

    The starts (``ld_k{j}`` / ``ld_v{j}``, one page each, into the other
    buffer) are SIP's movable set: the copies in flight while this block
    computes.  Each writes a page of its own, so the search may issue them
    in any order and at any point of the block; the waits (``wt_*``) and
    the attention chain keep program order."""
    kinds = ("k",) if shared_kv else ("k", "v")
    instrs: list[Instr] = []
    for kind in kinds:      # keys first: the scores wait on them first
        for j in range(pages_per_block):
            instrs.append(Instr(
                name=f"ld_{kind}{j}", kind=Kind.MEM, inputs=(), outputs=(),
                fn=functools.partial(_start_page, kind=kind, j=j),
                buffer=f"next_{kind}{j}", is_store=True, bytes=page_bytes))
    for kind in kinds:
        waits = tuple(f"{kind}{j}" for j in range(pages_per_block))
        for j, out in enumerate(waits):
            instrs.append(Instr(
                name=f"wt_{kind}{j}", kind=Kind.MEM, inputs=(),
                outputs=(out,), fn=functools.partial(_wait_page, kind=kind,
                                                     j=j),
                buffer=f"cur_{kind}{j}", bytes=0))
        if kind == "k":
            instrs.append(Instr(
                name="scores", kind=Kind.COMPUTE, inputs=waits,
                outputs=("p", "alpha", "m_new", "l_new"), fn=_scores,
                flops=2 * hg * cols * d))
    instrs.append(Instr(
        name="pv", kind=Kind.COMPUTE,
        inputs=("p", "alpha") + (() if shared_kv else tuple(
            f"v{j}" for j in range(pages_per_block))),
        outputs=("acc_new",), fn=_pv, flops=2 * hg * cols * dv))
    return Program(instrs, replications=total_blocks)


def _copy(env, kind: str, row, blk, slot, j: int):
    """The copy of page ``j`` of block ``blk`` of ``row`` into ``slot``."""
    page = env["pt_ref"][row * env["n"] + blk * env["ppb"] + j]
    page = jnp.clip(env["base_ref"][0] + page, 0, env["n_store"] - 1)
    src = env[f"{kind}_hbm"].at[page]
    if env["pack"] > 1:
        buf = env[f"{kind}_buf"]
        src = src.bitcast(buf.dtype).reshape(buf.shape[2:])
    return pltpu.make_async_copy(src, env[f"{kind}_buf"].at[slot, j],
                                 env["sems"].at[slot, 0 if kind == "k" else 1])


def _live(env, row, blk, j: int):
    return (blk * env["ppb"] + j) * env["ps"] < env["len_ref"][row]


def _start_page(env, kind: str, j: int):
    row, blk = env["next_row"], env["next_blk"]

    @pl.when(jnp.logical_and(row < env["b"], _live(env, row, blk, j)))
    def _():
        _copy(env, kind, row, blk, 1 - env["slot"], j).start()
    return {}


def _wait_page(env, kind: str, j: int):
    row, blk = env["row"], env["blk"]

    @pl.when(_live(env, row, blk, j))
    def _():
        _copy(env, kind, row, blk, env["slot"], j).wait()
    return {f"{kind}{j}": None}


def _block(env, kind: str):
    """This block's pages as (tokens * heads, lanes) rows: token-major,
    then kv head, as they lie in the page."""
    buf = env[f"{kind}_buf"][env["slot"]]
    buf = buf.reshape(-1, buf.shape[-1])
    if env["pack"] > 1:
        buf = pltpu.bitcast(buf, env["dtype"])
    return buf


def _scores(env):
    """Scores of every (kv head, group) query row against every (token, kv
    head) key row, in float32; a row keeps its own head's live tokens, the
    rest are masked.  Running max and the rescale of what came before."""
    k = _block(env, "k")
    q = env["q"].astype(k.dtype)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    s = s * env["scale_ref"][0]
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    live = (env["len_ref"][env["row"]] - env["blk"] * env["ppb"] * env["ps"])
    keep = col < live * env["hkv"]
    if env["hkv"] > 1:
        keep = jnp.logical_and(keep, env["head_ref"][...] != 0)
    s = jnp.where(keep, s, NEG_INF)
    m = jnp.maximum(env["m"], jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m)
    alpha = jnp.exp(env["m"] - m)
    return {"p": p, "alpha": alpha, "m_new": m,
            "l_new": alpha * env["l"] + jnp.sum(p, axis=1, keepdims=True)}


def _pv(env):
    v = _block(env, "k" if env["shared_kv"] else "v")
    pv = jnp.dot(env["p"].astype(v.dtype), v,
                 preferred_element_type=jnp.float32)
    return {"acc_new": env["alpha"] * env["acc"] + pv}


def paged_decode_attention(q: jax.Array, k_store: jax.Array,
                           page_table: jax.Array, base: jax.Array,
                           lengths: jax.Array, scale: jax.Array,
                           v_store: jax.Array | None = None, *,
                           pages_per_block: int, order=None,
                           interpret: bool | None = None) -> jax.Array:
    """Decode attention read straight from a paged store, one row's live
    pages at a time.

    q: (B, Hkv, G, D); k_store: (P, ps, Hkv, D), or (P, ps, D) for one kv
    head (a latent store); page_table: (B, n) int32, row ``b``'s page ``i``
    at store page ``base + page_table[b, i]``; lengths: (B,) int32 keys to
    attend (0: the row copies and computes nothing and reads zeros);
    scale: the score scale, a float32 scalar; v_store: like ``k_store``
    with its own last dim, or None where the key page is the value page.
    -> (B, Hkv, G, Dv) in the store's dtype.

    One grid step per row: a loop over the row's first ``ceil(len / ps)``
    pages, ``pages_per_block`` pages a block, their copies double-buffered
    from HBM (the store stays where it is) and the next row's first block
    copied while this row's last one computes.  The dots take the store's
    dtype for q, k, p and v, accumulating in float32 (what XLA's default
    precision does with float32 operands on the TPU); the softmax runs in
    float32 on running statistics."""
    if interpret is None:
        interpret = interpret_mode()
    b, hkv, g, d = q.shape
    n_store, ps, *mid, dk = k_store.shape
    assert dk == d and (mid == [hkv] or (not mid and hkv == 1)), \
        (q.shape, k_store.shape)
    n = page_table.shape[1]
    shared_kv = v_store is None
    kinds = ("k",) if shared_kv else ("k", "v")
    dv = dk if shared_kv else v_store.shape[-1]
    ppb, hg = pages_per_block, hkv * g
    cols = ppb * ps * hkv
    lens = jnp.clip(lengths.astype(jnp.int32), 0, n * ps)
    # the first row with keys at or after each row: who copies next
    idx = jnp.where(lens > 0, jnp.arange(b, dtype=jnp.int32), b)
    nxt = jnp.concatenate([jax.lax.cummin(idx, axis=0, reverse=True),
                           jnp.full((1,), b, jnp.int32)])
    esize = jnp.dtype(k_store.dtype).itemsize
    # a page's rows go to fast memory as 32-bit words, two 16-bit rows a
    # word: the store's tiles then read as the layout the MXU takes, with
    # no repacking of the block
    pack = 4 // esize if esize < 4 and all(
        s.shape[-2] % (4 // esize) == 0
        for s in (k_store, v_store) if s is not None) else 1
    program = decode_program(
        pages_per_block=ppb, page_bytes=ps * hkv * dk * esize,
        shared_kv=shared_kv, hg=hg, cols=cols, d=d, dv=dv,
        total_blocks=b * -(-n // ppb))
    # which key rows each query row may see: its own kv head's
    head = (jnp.arange(cols)[None, :] % hkv
            == jnp.arange(hg)[:, None] // g).astype(jnp.int32)

    def kernel(len_ref, pt_ref, base_ref, nxt_ref, q_ref, scale_ref,
               head_ref, k_hbm, *rest):
        if shared_kv:
            o_ref, k_buf, sems, slot_ref = rest
            v_hbm = v_buf = None
        else:
            v_hbm, o_ref, k_buf, v_buf, sems, slot_ref = rest
        row = pl.program_id(0)
        env0 = {"len_ref": len_ref, "pt_ref": pt_ref, "base_ref": base_ref,
                "scale_ref": scale_ref, "head_ref": head_ref,
                "k_hbm": k_hbm, "v_hbm": v_hbm, "k_buf": k_buf,
                "v_buf": v_buf, "sems": sems, "n": n, "ppb": ppb, "ps": ps,
                "b": b, "hkv": hkv, "n_store": n_store,
                "shared_kv": shared_kv, "pack": pack,
                "dtype": k_store.dtype}

        @pl.when(row == 0)
        def _():
            # what a partial block leaves unwritten is multiplied by a
            # zero weight: it must not hold a NaN
            k_buf[...] = jnp.zeros_like(k_buf)
            if v_buf is not None:
                v_buf[...] = jnp.zeros_like(v_buf)
            slot_ref[0] = 0
            env = dict(env0, next_row=nxt_ref[0], next_blk=0, slot=1)
            for kind in kinds:
                for j in range(ppb):
                    _start_page(env, kind, j)

        nb = (len_ref[row] + ppb * ps - 1) // (ppb * ps)

        def body(blk, carry):
            m, l, acc = carry
            slot = slot_ref[0]
            last = blk + 1 == nb
            env = dict(env0, row=row, blk=blk, slot=slot, q=q_ref[0],
                       next_row=jnp.where(last, nxt_ref[row + 1], row),
                       next_blk=jnp.where(last, 0, blk + 1),
                       m=m, l=l, acc=acc)
            env = program.execute(env, order)
            slot_ref[0] = 1 - slot
            return env["m_new"], env["l_new"], env["acc_new"]

        init = (jnp.full((hg, 1), NEG_INF, jnp.float32),
                jnp.zeros((hg, 1), jnp.float32),
                jnp.zeros((hg, dv), jnp.float32))
        _, l, acc = jax.lax.fori_loop(0, nb, body, init)
        o_ref[0] = jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0),
                             0.0).astype(o_ref.dtype)

    any_spec = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [
        pl.BlockSpec((1, hg, d), lambda r, *_: (r, 0, 0)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
        pl.BlockSpec((hg, cols), lambda r, *_: (0, 0)),
        any_spec,
    ]
    def buffer(store):
        """Two blocks of ``ppb`` pages, as 32-bit words when packed."""
        if pack == 1:
            return pltpu.VMEM((2, ppb) + store.shape[1:], store.dtype)
        rows = math.prod(store.shape[1:-1]) // pack
        return pltpu.VMEM((2, ppb, rows, store.shape[-1]), jnp.uint32)

    scratch = [buffer(k_store)]
    args = [q.reshape(b, hg, d), jnp.asarray(scale, jnp.float32).reshape(1),
            head, k_store]
    if not shared_kv:
        in_specs.append(any_spec)
        scratch.append(buffer(v_store))
        args.append(v_store)
    scratch += [pltpu.SemaphoreType.DMA((2, 2)), pltpu.SMEM((1,), jnp.int32)]
    kwargs = {}
    if not interpret:
        kwargs["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("arbitrary",))
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hg, dv), k_store.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(b,), in_specs=in_specs,
            out_specs=pl.BlockSpec((1, hg, dv), lambda r, *_: (r, 0, 0)),
            scratch_shapes=scratch),
        interpret=interpret, **kwargs,
    )(lens, page_table.astype(jnp.int32).reshape(-1),
      jnp.asarray(base, jnp.int32).reshape(1), nxt, *args)
    return out.reshape(b, hkv, g, dv)
