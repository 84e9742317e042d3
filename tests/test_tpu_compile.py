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


@pytest.mark.parametrize("d", [128, 80])
def test_paged_gather_default_schedule_compiles(one_chip, d):
    """The paged-KV read: a (P, 16, 8, d) bf16 page store through (B, n)
    page tables."""
    static = dict(p=273, ps=16, h=8, d=d, b=8, n=34, dtype="bfloat16")
    knobs = pg_ops.space(**static).default_knobs()
    store = jax.ShapeDtypeStruct((273, 16, 8, d), jnp.bfloat16,
                                 sharding=one_chip)
    pt = jax.ShapeDtypeStruct((8, 34), jnp.int32, sharding=one_chip)
    _compile(functools.partial(pg_kernel.paged_gather, interpret=False,
                               **knobs), store, pt)


def test_qwen3_paged_decode_step_fits_one_chip(one_chip, monkeypatch):
    """The engine's paged decode step at qwen3-1.7b's full width and depth
    (f32 parameters, bf16 compute, 8 slots of 544 tokens) compiles with its
    kernels and fits one chip's memory."""
    monkeypatch.setattr(pg_kernel, "interpret_mode", lambda: False)
    cfg = dataclasses.replace(configs.get("qwen3-1.7b"), use_pallas=True)
    cap, max_len, ps = 8, 544, 16
    n_pg = -(-max_len // ps)
    shapes = jax.eval_shape(lambda k: nn.unwrap(M.init_lm(k, cfg)),
                            jax.random.PRNGKey(0))
    ex = {"tokens": np.zeros((1, 8), np.int32)}
    caches = jax.eval_shape(lambda p: M.alloc_paged_caches(
        p, cfg, cap, max_len, ps, cap * n_pg + 1, ex)[0], shapes)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    decode = functools.partial(E._decode_sample_paged, cfg=cfg,
                               temperature=0.0)
    compiled = _compile(decode, on_chip(shapes), on_chip(caches),
                        arg((cap,)), arg((cap, n_pg)), arg((cap,), jnp.bool_),
                        key=arg((2,), jnp.uint32))
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM
