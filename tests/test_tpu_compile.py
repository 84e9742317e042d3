"""Compile the serving path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler builds for a topology that is described,
not attached, and refuses what the chip's compiler would refuse (a block
shape off the tiling, too much fast memory, a program larger than the
chip).  The topology is described inside a fixture, never while a module is
imported, so test workers that do not run this file never load the TPU
library.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.flash_attention import kernel as fa_kernel
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.paged_attention import kernel as pg_kernel
from repro.kernels.paged_attention import ops as pg_ops
from repro.models import model as M
from repro.models import modules as nn
from repro.serve import engine as E

V5E_HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # what is compiled for a described chip can be written to the persistent
    # cache but not read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args, **kwargs):
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("sq,d", [(37, 128), (48, 128), (512, 128),
                                  (512, 64), (512, 80), (512, 112)])
def test_flash_attention_default_schedule_compiles(one_chip, sq, d):
    """Causal GQA prefill attention, bf16, at the tiles ``space()`` offers
    by default — an odd prompt length included."""
    static = dict(b=1, hq=16, hkv=8, sq=sq, skv=sq, d=d, causal=True,
                  window=None, dtype="bfloat16")
    knobs = fa_ops.space(**static).default_knobs()
    q = jax.ShapeDtypeStruct((1, 16, sq, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 8, sq, d), jnp.bfloat16, sharding=one_chip)
    _compile(functools.partial(fa_kernel.pallas_attention, causal=True,
                               interpret=False, **knobs), q, kv, kv)


@pytest.mark.parametrize("page", [(8, 128), (8, 80), (640,)],
                         ids=["128", "80", "latent640"])
def test_paged_gather_default_schedule_compiles(one_chip, page):
    """The paged-KV read: a (P, 16, *page) bf16 page store through (B, n)
    page tables — per-head K/V pages (8, d), and MLA's latent pages (640,):
    the 512-wide ``c_kv`` and the 64-wide ``k_pe`` filled to 128 lanes."""
    h, d = page if len(page) == 2 else (1, page[0])
    static = dict(ps=16, h=h, d=d, b=8, n=34, dtype="bfloat16")
    knobs = pg_ops.space(**static).default_knobs()
    store = jax.ShapeDtypeStruct((273, 16, *page), jnp.bfloat16,
                                 sharding=one_chip)
    pt = jax.ShapeDtypeStruct((8, 34), jnp.int32, sharding=one_chip)
    _compile(functools.partial(pg_kernel.paged_gather, interpret=False,
                               **knobs), store, pt)


@pytest.mark.parametrize("ppb", [8, 16, 32, 64])
@pytest.mark.parametrize("page,g", [((8, 128), 2), ((640,), 16)],
                         ids=["gqa", "latent640"])
def test_paged_decode_attention_schedules_compile(one_chip, page, g, ppb):
    """The fused decode read at the serving cells' geometry, at each block
    size its space offers from 8 pages up: qwen3's 8 kv heads of 128 (K
    and V stores) and deepseek's one 640-lane latent "head" of 16 queries
    (the key page is the value page), bf16 stores of 16-token pages; a
    table width no block size divides."""
    hkv, d = page if len(page) == 2 else (1, page[0])
    shared = len(page) == 1
    static = dict(b=16, hkv=hkv, g=g, d=d, dv=d, ps=16, n=37,
                  shared_kv=shared, dtype="bfloat16")
    assert ppb in pg_ops.decode_space(**static).knobs[0].choices
    knobs = {"pages_per_block": ppb}
    store = jax.ShapeDtypeStruct((273, 16, *page), jnp.bfloat16,
                                 sharding=one_chip)
    q = jax.ShapeDtypeStruct((16, hkv, g, d),
                             jnp.float32 if shared else jnp.bfloat16,
                             sharding=one_chip)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    args = (q, store, arg((16, 37)), arg(()), arg((16,)),
            arg((), jnp.float32)) + (() if shared else (store,))
    compiled = _compile(functools.partial(pg_kernel.paged_decode_attention,
                                          interpret=False, **knobs), *args)
    # the stores are read where they lie
    assert not [m.group(0) for m in _HLO_OP.finditer(compiled.as_text())
                if m.group(1).startswith("273,16,")
                and m.group(2) in ("copy", "transpose", "fusion")]


#: the paged cache of the qwen3-1.7b step compiles: slots, max_len, page
CAP, MAX_LEN, PS = 8, 544, 16
N_PG = -(-MAX_LEN // PS)
#: deepseek-v2-lite's: the doc-qa cell's slots, max_len and page pool
DS_CAP, DS_MAX_LEN, DS_PAGES = 16, 18944, 8000
DS_N_PG = -(-DS_MAX_LEN // PS)

#: an HLO instruction's result dims and opcode
_HLO_OP = re.compile(r"= \w+\[([\d,]*)\]\{[^}]*\} ([\w-]+)\(")


def _qwen3_paged(one_chip, monkeypatch):
    """qwen3-1.7b at full width and depth with compiled kernels (f32
    parameters, bf16 compute) over the paged cache above: (cfg, parameter
    shapes, cache shapes, cache axes, an argument maker) on the described
    chip."""
    cfg = dataclasses.replace(configs.get("qwen3-1.7b"), use_pallas=True)
    return _paged(one_chip, monkeypatch, cfg, CAP, MAX_LEN, CAP * N_PG + 1)


def _deepseek_paged(one_chip, monkeypatch):
    """deepseek-v2-lite as the doc-qa cell serves it: published widths, all
    27 layers, routed experts 0-15, bf16 parameters and compute, with
    compiled kernels over the cell's latent page pool."""
    cfg = dataclasses.replace(configs.get("deepseek-v2-lite"),
                              experts_held=16, param_dtype="bfloat16",
                              dtype="bfloat16", use_pallas=True)
    return _paged(one_chip, monkeypatch, cfg, DS_CAP, DS_MAX_LEN, DS_PAGES)


def _paged(one_chip, monkeypatch, cfg, cap, max_len, pages):
    monkeypatch.setattr(pg_kernel, "interpret_mode", lambda: False)
    shapes = jax.eval_shape(lambda k: nn.unwrap(M.init_lm(k, cfg)),
                            jax.random.PRNGKey(0))
    ex = {"tokens": np.zeros((1, 8), np.int32)}
    caches = jax.eval_shape(lambda p: M.alloc_paged_caches(
        p, cfg, cap, max_len, PS, pages, ex)[0], shapes)
    axes = M.paged_cache_axes(shapes, cfg, max_len, PS, ex)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return cfg, on_chip(shapes), on_chip(caches), axes, arg


def test_qwen3_paged_decode_step_fits_one_chip(one_chip, monkeypatch):
    """The engine's paged decode step at qwen3-1.7b's full width and depth
    (f32 parameters, bf16 compute, 8 slots of 544 tokens) compiles with its
    kernels and fits one chip's memory."""
    cfg, shapes, caches, _, arg = _qwen3_paged(one_chip, monkeypatch)
    decode = functools.partial(E._decode_sample_paged, cfg=cfg,
                               temperature=0.0)
    compiled = _compile(decode, shapes, caches, arg((CAP,)),
                        arg((CAP, N_PG)), arg((CAP,), jnp.bool_),
                        key=arg((2,), jnp.uint32))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM


def _compile_step(program, cfg, shapes, caches, axes, arg, cap, n_pg):
    """The engine's paged decode or chunk step, its cache donated as the
    engine dispatches it, compiled for the described chip."""
    if program == "decode":
        fn = functools.partial(E._decode_sample_paged, cfg=cfg,
                               temperature=0.0)
        args = (arg((cap,)), arg((cap, n_pg)), arg((cap,), jnp.bool_))
        kw = {"key": arg((2,), jnp.uint32)}
    else:
        fn = functools.partial(M.prefill_chunk, cfg=cfg, axes=axes)
        args, kw = (arg((1, 256)), arg((1, n_pg)), arg(()), arg(())), {}
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        shapes, caches, *args, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _gathered_views(compiled, caches, cap, n_pg) -> list[str]:
    """Ops that build a slot batch's whole-table view of a page store:
    (B, n, ps, ...) pages or (B, n*ps, ...) positions."""
    store = next(a for n, a in caches.items() if n != "len")
    page = ",".join(map(str, store.shape[3:]))
    ps = store.shape[2]
    views = {f"{cap},{n_pg},{ps},{page}", f"{cap},{n_pg * ps},{page}"}
    return [m.group(0) for m in _HLO_OP.finditer(compiled.as_text())
            if m.group(1) in views]


def _store_rewrites(compiled, caches) -> list[str]:
    """Ops that rebuild or copy a whole page store."""
    stores = [a for n, a in caches.items() if n != "len"]
    l, pool = stores[0].shape[:2]
    return [m.group(0) for m in _HLO_OP.finditer(compiled.as_text())
            if m.group(1).startswith((f"{l},{pool},", f"{l * pool},"))
            and m.group(2) in ("dynamic-update-slice", "concatenate",
                               "copy")]


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_qwen3_paged_steps_write_the_store_in_place(one_chip, monkeypatch,
                                                    program):
    """On the chip's compiler too, the paged decode and chunk steps, their
    cache donated as the engine dispatches them, write the page store where
    it lies: no op rebuilds or copies a whole store."""
    cfg, shapes, caches, axes, arg = _qwen3_paged(one_chip, monkeypatch)
    compiled = _compile_step(program, cfg, shapes, caches, axes, arg, CAP,
                             N_PG)
    bad = _store_rewrites(compiled, caches)
    assert not bad, "\n".join(bad)


@pytest.mark.parametrize("family", ["qwen3", "deepseek"])
def test_paged_decode_steps_read_no_gathered_view(one_chip, monkeypatch,
                                                  family):
    """On the chip's compiler, the engine's paged decode step reads each
    row's pages through the fused kernel: no op builds the slots' whole
    tables as a view, and no op copies a whole store."""
    if family == "qwen3":
        cfg, shapes, caches, axes, arg = _qwen3_paged(one_chip, monkeypatch)
        cap, n_pg = CAP, N_PG
    else:
        cfg, shapes, caches, axes, arg = _deepseek_paged(one_chip,
                                                         monkeypatch)
        cap, n_pg = DS_CAP, DS_N_PG
    compiled = _compile_step("decode", cfg, shapes, caches, axes, arg, cap,
                             n_pg)
    bad = _gathered_views(compiled, caches, cap, n_pg) + \
        _store_rewrites(compiled, caches)
    assert not bad, "\n".join(bad)


@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_deepseek_paged_steps_write_the_latent_stores_in_place(
        one_chip, monkeypatch, program):
    """deepseek-v2-lite's paged decode and chunk steps at the doc-qa cell's
    sizes: the latent (27, 8000, 16, 640) store is written where it lies,
    the routed experts run as grouped matmuls, and the step fits the chip (when first compiled: 14,244,527,104 B of
    arguments, 9.82 GB of weights and 4.42 GB of pages, and 389,456,896 B
    of temporaries in decode)."""
    cfg, shapes, caches, axes, arg = _deepseek_paged(one_chip, monkeypatch)
    assert caches["latent"].shape == (27, DS_PAGES, PS, 640)
    compiled = _compile_step(program, cfg, shapes, caches, axes, arg, DS_CAP,
                             DS_N_PG)
    bad = _store_rewrites(compiled, caches)
    assert not bad, "\n".join(bad)
    assert "ragged-dot" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30
