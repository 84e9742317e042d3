"""Plain causal GQA attention in float32, and its float8 control.

It imports nothing of the program.  ``quant="fp8"`` rounds q, k and v to
float8 e4m3 (per-tensor scale) first: the precision step below the bfloat16
the tuned kernel is served in.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.dense_lm import _q

HI = jax.lax.Precision.HIGHEST


def causal(q, k, v, quant: str | None = None):
    """q: (B, Hq, S, D); k, v: (B, Hkv, S, D) -> (B, Hq, S, D) float32."""
    q, k, v = (_q(x.astype(jnp.float32), quant) for x in (q, k, v))
    b, hq, s, d = q.shape
    g = hq // k.shape[1]
    qg = q.reshape(b, k.shape[1], g, s, d)
    sc = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k, precision=HI) * d ** -0.5
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhgqk,bhkd->bhgqd", p, v, precision=HI)
    return o.reshape(b, hq, s, d)


def rel_err(got, want) -> float:
    """Largest elementwise error, over the reference's largest magnitude."""
    got = jnp.asarray(got, jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
