"""Oracle for the paged KV gather: a plain dense take along the page axis."""

from __future__ import annotations

import jax.numpy as jnp


def paged_gather(store: jnp.ndarray, page_table: jnp.ndarray) -> jnp.ndarray:
    """store: (P, ps, [H,] D); page_table: (B, n) int32 -> (B, n, ps, [H,]
    D).

    ``out[b, i] = store[page_table[b, i]]`` — the cache-read indirection of
    paged attention.  Reshaping the result to (B, n*ps, H, D) yields the
    per-slot contiguous KV view the dense attention math consumes.
    """
    return jnp.take(store, page_table, axis=0)
