"""The fused paged decode read (``paged_decode_attention``) against its
dense oracle and against the gathered view through the model's attention,
on the CPU (Pallas interpret mode): GQA pages and MLA-like latent pages
whose key is the value, rows of length 0, 1, a page boundary and a full
table, idle rows, pages shared between rows in random order, and block
sizes that do not divide the table."""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels.paged_attention import kernel as K
from repro.kernels.paged_attention import ops as pg_ops
from repro.kernels.paged_attention import ref
from repro.models import attention as A

PS, N, BASE = 8, 5, 11          # 8-token pages, 5-page tables, a layer base
#: per-row lengths: idle, one token, a page boundary, two pages and a bit,
#: a full table, idle again (an inactive row), mid-page
LENS = np.array([0, 1, PS, 2 * PS + 3, N * PS, 0, 3 * PS - 1], np.int32)
#: (kv heads, query group, head dim, the key page is the value page)
FAMILIES = {"gqa": (2, 2, 128, False), "mla": (1, 4, 256, True)}


def _inputs(family: str, dtype):
    hkv, g, d, shared = FAMILIES[family]
    rng = np.random.default_rng(7)
    b = len(LENS)
    pool = 2 * N + 3
    page = (PS, d) if shared else (PS, hkv, d)
    store = lambda: jnp.asarray(rng.standard_normal(
        (BASE + pool,) + page), dtype)
    k = store()
    v = None if shared else store()
    # rows share pages, each table in its own random order
    pt = np.stack([rng.permutation(pool)[:N] for _ in range(b)])
    pt[3, :2] = pt[4, 3:5]
    q = jnp.asarray(rng.standard_normal((b, hkv, g, d)), dtype)
    return q, k, v, pt.astype(np.int32)


def _gathered(q, k, v, pt, scale):
    """The gathered view through the model's float32 attention."""
    b, hkv, g, d = q.shape
    ids = jnp.asarray(pt) + BASE
    kv = jnp.take(k, ids, axis=0).reshape(b, N * PS, hkv, d)
    vv = kv if v is None else jnp.take(v, ids, axis=0).reshape(
        b, N * PS, hkv, -1)
    o = A._sdpa(q.reshape(b, 1, hkv * g, d), kv, vv, causal=True,
                window=None, kv_len=jnp.asarray(LENS), scale=scale)
    return np.asarray(o, np.float32).reshape(b, hkv, g, -1)


@pytest.mark.parametrize("family,ppb,dtype", [
    ("gqa", 1, "float32"), ("gqa", 2, "float32"), ("gqa", 4, "float32"),
    ("gqa", 8, "float32"), ("gqa", 2, "bfloat16"),
    ("mla", 1, "float32"), ("mla", 2, "float32"), ("mla", 4, "float32"),
    ("mla", 8, "float32"), ("mla", 4, "bfloat16")])
def test_fused_read_matches_the_gathered_attention(family, ppb, dtype):
    dt = jnp.dtype(dtype)
    q, k, v, pt = _inputs(family, dt)
    scale = q.shape[-1] ** -0.5
    args = (q, k, jnp.asarray(pt), jnp.int32(BASE), jnp.asarray(LENS),
            jnp.float32(scale)) + (() if v is None else (v,))
    got = K.paged_decode_attention(*args, pages_per_block=ppb)
    assert got.shape == q.shape[:3] + (k.shape[-1] if v is None
                                       else v.shape[-1],)
    assert got.dtype == dt
    got = np.asarray(got, np.float32)
    want = np.asarray(ref.paged_decode_attention(*args), np.float32)
    # bfloat16: the dots round p to the store's dtype, as the TPU's
    # default precision does with float32 operands
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    live = LENS > 0
    np.testing.assert_allclose(got[live], _gathered(q, k, v, pt,
                                                    scale)[live],
                               rtol=tol, atol=tol)
    assert not np.any(got[~live]), "an idle row reads zeros"
