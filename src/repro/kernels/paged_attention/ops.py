"""SIP integration for the paged-KV reads (registry-based).

Two kernels: ``paged_gather``, the page-table-indirect cache read the
paged chunk step puts in front of attention, and
``paged_decode_attention``, the decode step's read and attention over
each row's live pages in one kernel.  Registered declaratively so
``launch/tune.py --smoke`` tunes them like any other kernel and the
serving engine resolves the ONE registry-cached instance bound to the
active ``schedule_cache`` scope.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.registry import KernelHandle, Workload, registry, sip_kernel
from repro.core.schedule import KnobSpec, Schedule, SearchSpace
from repro.kernels.paged_attention import kernel as K
from repro.kernels.paged_attention import ref

NAME = "paged_gather"
DECODE_NAME = "paged_decode_attention"


def _divisors(dim: int, prefs: tuple[int, ...]) -> tuple[int, ...]:
    ch = tuple(c for c in prefs if dim % c == 0 and c <= dim)
    return ch or (1,)


def space(*, ps, h, d, b, n, dtype="float32") -> SearchSpace:
    """Copy-tiling knobs: ``rows`` splits the page's ps positions into row
    blocks, ``n_chunks`` splits the head dim — together they set the tile
    count of the movable load/store stream."""
    return SearchSpace(knobs=(
        KnobSpec("rows", _divisors(ps, (1, 2, 4, 8))),
        KnobSpec("n_chunks", _divisors(d, (1, 2, 4))),
    ))


def _knobs(schedule: Schedule, **static):
    sp = space(**static)
    k = sp.default_knobs()
    k.update(schedule.knobs)
    return k["rows"], k["n_chunks"]


def program_for(schedule: Schedule, **static):
    rows, n_chunks = _knobs(schedule, **static)
    return K.make_program(ps=static["ps"], h=static["h"], d=static["d"],
                          rows=rows, n_chunks=n_chunks,
                          dtype=jnp.dtype(static["dtype"]),
                          total_pages=static["b"] * static["n"])


def signature_fn(store, page_table) -> dict:
    """The store's page count is left out: no knob or program depends on
    it, and the serving path gathers from a store that holds every layer's
    pages, so a schedule tuned on one layer's pool serves it too.  A latent
    store's (ps, D) page is keyed as (ps, 1, D): the same copy program."""
    _, ps, *mid, d = store.shape
    h = mid[0] if mid else 1
    b, n = page_table.shape
    return {"ps": int(ps), "h": int(h), "d": int(d),
            "b": int(b), "n": int(n), "dtype": str(jnp.dtype(store.dtype))}


def _gather_args(p: int, ps: int, h: int, d: int, b: int, n: int):
    def make_args(rng: np.random.Generator):
        store = rng.standard_normal((p, ps, h, d)).astype(np.float32)
        pt = rng.integers(0, p, (b, n)).astype(np.int32)
        return [store, pt]
    return make_args


@sip_kernel(
    name=NAME, program_for=program_for, space_for=space,
    oracle=ref.paged_gather, signature_fn=signature_fn,
    workloads=[
        Workload("smoke_p8_ps8_h2_d8_b2_n4", _gather_args(8, 8, 2, 8, 2, 4),
                 suites=("smoke",)),
        Workload("deploy_p64_ps16_h4_d32_b8_n8",
                 _gather_args(64, 16, 4, 32, 8, 8)),
    ])
def build(schedule: Schedule, **static):
    rows, n_chunks = _knobs(schedule, **static)
    program = program_for(schedule, **static)
    order = schedule.resolve_order(program)
    fn = functools.partial(K.paged_gather, rows=rows, n_chunks=n_chunks,
                           order=order)
    return jax.jit(fn)


def kernel():
    """The shared registry instance bound to the active schedule cache —
    the serving resolution path."""
    return registry.get(NAME)


# late-binding handle: honors the schedule_cache scope active at call time
paged_gather = KernelHandle(NAME)


# ------------------------------------------------- fused paged decode read
#: fast memory the double-buffered page blocks may take (of the v5e's
#: 16 MiB scoped default)
_BLOCK_BUDGET = 8 << 20
#: bytes a block's copies should move: enough in flight to cover HBM's
#: latency at its bandwidth (~1.5 us at 819 GB/s on a v5e)
_BLOCK_TARGET = 1 << 20


def decode_space(*, b, hkv, g, d, dv, ps, n, shared_kv,
                 dtype="float32") -> SearchSpace:
    """``pages_per_block``: the pages one step of a row's loop copies and
    attends over — fewer, longer steps with more bytes in flight against
    more masked work past a row's last live page.  Ordered by how near a
    block's bytes come to ``_BLOCK_TARGET`` (the larger first on a tie):
    the first is the default schedule."""
    page = ps * hkv * jnp.dtype(dtype).itemsize * (d if shared_kv else d + dv)
    # a block past the table's first power of two only adds masked work
    fits = [c for c in (64, 32, 16, 8, 4, 2, 1)
            if 2 * c * page <= _BLOCK_BUDGET and c < 2 * n] or [1]
    fits.sort(key=lambda c: abs(math.log2(c * page / _BLOCK_TARGET)))
    return SearchSpace(knobs=(KnobSpec("pages_per_block", tuple(fits)),))


def _decode_knob(schedule: Schedule, **static) -> int:
    k = decode_space(**static).default_knobs()
    k.update(schedule.knobs)
    return k["pages_per_block"]


def decode_program_for(schedule: Schedule, **static):
    ppb = _decode_knob(schedule, **static)
    esize = jnp.dtype(static["dtype"]).itemsize
    return K.decode_program(
        pages_per_block=ppb,
        page_bytes=static["ps"] * static["hkv"] * static["d"] * esize,
        shared_kv=static["shared_kv"], hg=static["hkv"] * static["g"],
        cols=ppb * static["ps"] * static["hkv"], d=static["d"],
        dv=static["dv"], total_blocks=static["b"] * -(-static["n"] // ppb))


def decode_signature_fn(q, k_store, page_table, base, lengths, scale,
                        v_store=None) -> dict:
    """As the gather's: the store's page count is left out, so a schedule
    tuned on one layer's pool serves the store of every layer."""
    b, hkv, g, d = q.shape
    ps = k_store.shape[1]
    dv = k_store.shape[-1] if v_store is None else v_store.shape[-1]
    return {"b": int(b), "hkv": int(hkv), "g": int(g), "d": int(d),
            "dv": int(dv), "ps": int(ps), "n": int(page_table.shape[1]),
            "shared_kv": v_store is None,
            "dtype": str(jnp.dtype(k_store.dtype))}


def decode_args(p: int, ps: int, hkv: int, g: int, d: int, b: int, n: int,
                shared_kv: bool = False, dtype: str = "float32"):
    """Example arguments: a (P, ps, Hkv, D) store (or a (P, ps, D) latent
    one), random page tables and lengths over the whole table."""
    def make_args(rng: np.random.Generator):
        dt = np.dtype(dtype)
        page = (ps, d) if shared_kv else (ps, hkv, d)
        q = rng.standard_normal((b, hkv, g, d)).astype(dt)
        k = rng.standard_normal((p,) + page).astype(dt)
        pt = rng.integers(0, p, (b, n)).astype(np.int32)
        lens = rng.integers(0, n * ps + 1, (b,)).astype(np.int32)
        args = [q, k, pt, np.int32(0), lens, np.float32(d ** -0.5)]
        if not shared_kv:
            args.append(rng.standard_normal((p,) + page).astype(dt))
        return args
    return make_args


@sip_kernel(
    name=DECODE_NAME, program_for=decode_program_for, space_for=decode_space,
    oracle=ref.paged_decode_attention, signature_fn=decode_signature_fn,
    workloads=[
        Workload("smoke_p8_ps8_h2_g2_d8_b2_n4",
                 decode_args(8, 8, 2, 2, 8, 2, 4), suites=("smoke",)),
        Workload("deploy_p64_ps16_h4_g2_d32_b8_n8",
                 decode_args(64, 16, 4, 2, 32, 8, 8)),
        Workload("deploy_latent_p64_ps16_g4_d128_b4_n8",
                 decode_args(64, 16, 1, 4, 128, 4, 8, shared_kv=True)),
    ])
def build_decode(schedule: Schedule, **static):
    ppb = _decode_knob(schedule, **static)
    order = schedule.resolve_order(decode_program_for(schedule, **static))
    return jax.jit(functools.partial(K.paged_decode_attention,
                                     pages_per_block=ppb, order=order))


paged_decode_attention = KernelHandle(DECODE_NAME)
