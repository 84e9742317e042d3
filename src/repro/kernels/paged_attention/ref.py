"""Oracles for the paged KV reads: the gather as a plain dense take along
the page axis, and the fused decode read as that take followed by dense
masked attention in float32."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def paged_gather(store: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """store: (P, ps, [H,] D); page_table: (B, n) int32 -> (B, n, ps, [H,]
    D).

    ``out[b, i] = store[page_table[b, i]]`` — the cache-read indirection of
    paged attention.  Reshaping the result to (B, n*ps, H, D) yields the
    per-slot contiguous KV view the dense attention math consumes.
    """
    return jnp.take(store, page_table, axis=0)


def paged_decode_attention(q, k_store, page_table, base, lengths, scale,
                           v_store=None):
    """q: (B, Hkv, G, D); k_store: (P, ps, Hkv, D) or (P, ps, D);
    page_table: (B, n); base: scalar; lengths: (B,); scale: scalar;
    v_store: like ``k_store``, or None (keys are values) -> (B, Hkv, G, Dv)
    in the store's dtype.

    Row ``b`` attends over the first ``lengths[b]`` positions of its pages
    ``base + page_table[b]`` (clamped into the store; lengths clamped into
    the table), all in float32; a row of length 0 reads zeros."""
    v_store = k_store if v_store is None else v_store
    b, hkv, g, d = q.shape
    n_store, ps = k_store.shape[:2]
    n = page_table.shape[1]
    ids = jnp.clip(jnp.asarray(base, jnp.int32) + page_table, 0, n_store - 1)
    k = paged_gather(k_store, ids).reshape(b, n * ps, hkv, d)
    v = paged_gather(v_store, ids).reshape(b, n * ps, hkv, -1)
    lens = jnp.clip(lengths, 0, n * ps)
    s = jnp.einsum("bhgd,bthd->bhgt", q.astype(jnp.float32),
                   k.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    s = s * jnp.asarray(scale, jnp.float32).reshape(())
    live = jnp.arange(n * ps)[None, :] < lens[:, None]
    s = jnp.where(live[:, None, None], s, -jnp.inf)
    p = jax.nn.softmax(jnp.where(lens[:, None, None, None] > 0, s, 0.0),
                       axis=-1)
    o = jnp.einsum("bhgt,bthd->bhgd", p, v.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)
    o = jnp.where(lens[:, None, None, None] > 0, o, 0.0)
    return o.astype(k_store.dtype)
