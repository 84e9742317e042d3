"""The dense decoder-LM family's model module (the qwen3 / internlm2
block: RMSNorm, GQA attention with rotary positions and optional per-head
q/k norm, SwiGLU MLP, an output head tied to the embedding or its own).

A configuration file names its model module under ``"model"``; the serving
harness reaches the family only through it (``bench/serve.py``,
``model_module``): its sizes (``Spec``), the check that the program serves
them (``check_program``), its weights in the program's layout
(``program_params``, ``make``), its plain float32 reference (``logits``)
and its counts of work (``token_flops``, ``chunk_flops``,
``kv_read_bytes``).  The weights and the FLOP counts are
``bench/weights.py``'s and ``bench/flops.py``'s.

The reference imports nothing of the program.  Its weights come from
``bench.weights`` and the seed, one layer at a time, so a whole model never
has to fit in float32 at once; the sequences of a sample run together,
padded at the end to one fixed length (causal attention keeps padding out
of every real position), so one compiled block serves every run.

``quant="fp8"`` is the control: the same forward with both operands of
every projection rounded to float8 e4m3 (per-tensor scale), the precision
step below the bfloat16 the configurations serve in.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.flops import chunk_flops, token_flops
from bench.weights import Spec, make, program_params

__all__ = ["Spec", "check_program", "program_params", "make", "logits",
           "token_flops", "chunk_flops", "kv_read_bytes"]

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _q(x, quant):
    if quant is None:
        return x
    if quant != "fp8":
        raise ValueError(f"unknown quantization {quant!r}")
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(eq, a, b, quant):
    return jnp.einsum(eq, _q(a, quant), _q(b, quant), precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    s, half = x.shape[0], x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attend(q, k, v):
    """One sequence: q (S, H, D), k/v (S, Hkv, D), causal."""
    s, h, d = q.shape
    g = h // k.shape[1]
    qg = q.reshape(s, k.shape[1], g, d)
    sc = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HI) * d ** -0.5
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
    return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI).reshape(s, h, d)


@functools.partial(jax.jit, static_argnames=("spec", "quant"))
def _block(x, w, *, spec: W.Spec, quant):
    f32 = lambda a: a.astype(jnp.float32)
    a = w["attn"]

    def one(xs):
        h = _rms(xs, f32(w["ln1"]), spec.rms_eps)
        q = _mm("sd,dhk->shk", h, f32(a["wq"]), quant)
        k = _mm("sd,dhk->shk", h, f32(a["wk"]), quant)
        v = _mm("sd,dhk->shk", h, f32(a["wv"]), quant)
        if spec.qk_norm:
            q = _rms(q, f32(a["q_norm"]), spec.rms_eps)
            k = _rms(k, f32(a["k_norm"]), spec.rms_eps)
        q, k = _rope(q, spec.rope_theta), _rope(k, spec.rope_theta)
        xs = xs + _mm("shk,hkd->sd", _attend(q, k, v), f32(a["wo"]), quant)
        h = _rms(xs, f32(w["ln2"]), spec.rms_eps)
        ff = w["ffn"]
        u = (jax.nn.silu(_mm("sd,df->sf", h, f32(ff["w_gate"]), quant))
             * _mm("sd,df->sf", h, f32(ff["w_up"]), quant))
        return xs + _mm("sf,fd->sd", u, f32(ff["w_down"]), quant)

    return jax.lax.map(one, x)


@functools.partial(jax.jit, static_argnames=("spec",))
def _layer_weights(key, l, *, spec: W.Spec):
    return W.layer(key, l, spec, jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("spec",))
def _top_weights(key, *, spec: W.Spec):
    return W.top(key, spec, jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("spec", "quant"))
def _head(x, rows, t, *, spec: W.Spec, quant):
    """Logits at ``rows`` (B, R) of the final hidden states."""
    h = jnp.take_along_axis(x, rows[..., None], axis=1)
    h = _rms(h, t["ln_f"].astype(jnp.float32), spec.rms_eps)
    if spec.tied:
        return _mm("brd,vd->brv", h, t["embed"].astype(jnp.float32), quant)
    return _mm("brd,dv->brv", h, t["lm_head"].astype(jnp.float32), quant)


KV_ITEMSIZE = 2         # bytes of a cached K or V element: bfloat16


def check_program(cfg, spec: W.Spec) -> None:
    """Raise unless the program's ``ModelConfig`` is a dense SwiGLU decoder
    of the sizes the configuration file states, attending to every
    position (no window)."""
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
           cfg.d_ff, cfg.vocab, cfg.qk_norm, cfg.rope_theta, cfg.family,
           cfg.mlp_type, cfg.window)
    want = (spec.layers, spec.d_model, spec.heads, spec.kv_heads,
            spec.head_dim, spec.d_ff, spec.vocab, spec.qk_norm,
            spec.rope_theta, "dense", "swiglu", None)
    if got != want:
        raise ValueError(f"program config {cfg.name!r} gives {got}, the "
                         f"configuration file states {want}")


def kv_read_bytes(spec: W.Spec, ctx: int, page_size: int) -> float:
    """Cache bytes that attention for one row at context ``ctx`` must read:
    K and V of each of its ``ceil(ctx / page_size)`` live pages, every
    layer, once."""
    pages = -(-ctx // page_size)
    return (2.0 * spec.layers * pages * page_size * spec.kv_heads
            * spec.head_dim * KV_ITEMSIZE)


def logits(seed: int, spec: W.Spec, seqs, reads, *, pad_to: int,
           quant: str | None = None) -> list[np.ndarray]:
    """Float32 logits of each sequence at its read positions.

    ``seqs``: token arrays, each at most ``pad_to`` long; ``reads``: for
    each, the positions whose next-token logits are wanted.  Returns one
    (len(reads[i]), vocab) array per sequence, on the host."""
    if any(len(s) > pad_to for s in seqs):
        raise ValueError(f"a sequence is longer than pad_to={pad_to}")
    key = W.seed_key(seed)
    toks = np.zeros((len(seqs), pad_to), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    r = max(len(x) for x in reads)
    rows = np.zeros((len(seqs), r), np.int32)
    for i, x in enumerate(reads):
        rows[i, :len(x)] = x
        rows[i, len(x):] = x[-1] if len(x) else 0
    t = _top_weights(key, spec=spec)
    x = t["embed"].astype(jnp.float32)[jnp.asarray(toks)]
    del t
    for l in range(spec.layers):
        x = _block(x, _layer_weights(key, l, spec=spec), spec=spec,
                   quant=quant)
    out = np.asarray(_head(x, jnp.asarray(rows), _top_weights(key, spec=spec),
                           spec=spec, quant=quant))
    return [out[i, :len(x)] for i, x in enumerate(reads)]
