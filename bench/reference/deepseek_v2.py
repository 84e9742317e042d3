"""The DeepSeek-V2 family's model module: multi-head latent attention (MLA)
with YaRN rope, a leading dense SwiGLU layer, then MoE layers of routed
experts (softmax top-k, greedy) and shared experts, untied head.

A configuration file names this module under ``"model"``; the serving
harness reaches the family only through it (``bench/serve.py``,
``model_module``).  The configuration holds one device's expert share: of
the published ``routed_experts_published`` routed experts, experts
``first_held_expert`` .. ``+ n_routed_experts`` live here.  The router keeps
its published width and top-k; what absent experts would add is left out,
here as in the program.

The reference imports nothing of the program.  It is the published
modeling code's forward in float32 (``HI`` einsums, every projection at
``Precision.HIGHEST``), one layer at a time, with no cache, kernels or
batching: keys and values expanded per head from the latent, and every held
expert computed for every token and weighted by its gate (zero where the
token did not pick it).  So that a sample of long sequences fits beside the
serving engine, which the harness still holds when it checks, sequences run
one at a time, attention in query blocks and the FFN in token blocks; each
is padded at the end to the sample's longest, in whole blocks (causal
attention keeps padding out of every real position).

Equations (DeepSeek-V2 ``modeling_deepseek.py``):

- ``q = h Wq`` split into ``q_nope`` (128) and ``q_pe`` (64) per head;
  ``[c_kv, k_pe] = h W_kv_a``; ``c_kv`` RMS-normed; ``k = [c_kv W_UK,
  k_pe]`` (``k_pe`` shared by the heads), ``v = c_kv W_UV``;
- rope on ``q_pe`` and ``k_pe`` after de-interleaving their dims (pairs
  (x0, x1), (x2, x3) ... become x0 x2 ... | x1 x3 ...), then
  ``x cos + rotate_half(x) sin`` with YaRN's frequencies; cos/sin scaled by
  ``m(mscale) / m(mscale_all_dim)`` (1 here), ``m(s) = 0.1 s ln(factor) + 1``;
- scores scaled by ``(nope + rope) ** -0.5 * m(mscale_all_dim) ** 2``;
- gates: ``softmax(h W_router)``, top-k, renormalized only where
  ``norm_topk_prob``, times ``routed_scaling_factor`` (1);
- MoE output: the gated sum of the held experts' SwiGLUs plus the shared
  experts' SwiGLU of width ``n_shared_experts * moe_intermediate_size``.

``quant="fp8"`` is the control: the same forward with both operands of
every projection rounded to float8 e4m3 (per-tensor scale), the precision
step below the bfloat16 the configuration serves in.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.reference.dense_lm import HI, _q

__all__ = ["Spec", "check_program", "program_params", "make", "logits",
           "token_flops", "chunk_flops", "kv_read_bytes"]

KV_ITEMSIZE = 2         # bytes of a cached latent element: bfloat16
BLOCK = 256             # the reference's query and token block


@dataclasses.dataclass(frozen=True)
class Spec:
    """The published sizes of a DeepSeek-V2 model, and the expert share a
    configuration file states."""

    layers: int
    dense_layers: int           # first_k_dense_replace
    d_model: int
    heads: int
    kv_rank: int                # kv_lora_rank
    nope: int                   # qk_nope_head_dim
    rope: int                   # qk_rope_head_dim
    v_dim: int                  # v_head_dim
    dense_ff: int               # intermediate_size
    expert_ff: int              # moe_intermediate_size
    experts: int                # routed experts, published
    experts_held: int
    first_expert: int
    top_k: int
    shared: int                 # n_shared_experts
    norm_topk: bool
    vocab: int
    rms_eps: float
    rope_theta: float
    yarn: tuple                 # factor, original max, beta fast, beta slow,
                                # mscale, mscale_all_dim

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    @classmethod
    def from_config(cls, c: dict) -> "Spec":
        fixed = {"q_lora_rank": None, "hidden_act": "silu",
                 "scoring_func": "softmax", "topk_method": "greedy",
                 "routed_scaling_factor": 1, "n_group": 1, "topk_group": 1,
                 "moe_layer_freq": 1, "attention_bias": False,
                 "tie_word_embeddings": False}
        for k, v in fixed.items():
            if c[k] != v:
                raise ValueError(f"the reference has {k} = {v!r}, the "
                                 f"configuration file {c[k]!r}")
        r = c["rope_scaling"]
        if r["type"] != "yarn":
            raise ValueError(f"rope_scaling {r['type']!r} is not yarn")
        return cls(
            layers=c["num_hidden_layers"],
            dense_layers=c["first_k_dense_replace"],
            d_model=c["hidden_size"], heads=c["num_attention_heads"],
            kv_rank=c["kv_lora_rank"], nope=c["qk_nope_head_dim"],
            rope=c["qk_rope_head_dim"], v_dim=c["v_head_dim"],
            dense_ff=c["intermediate_size"],
            expert_ff=c["moe_intermediate_size"],
            experts=c["routed_experts_published"],
            experts_held=c["n_routed_experts"],
            first_expert=c["first_held_expert"],
            top_k=c["num_experts_per_tok"], shared=c["n_shared_experts"],
            norm_topk=bool(c["norm_topk_prob"]), vocab=c["vocab_size"],
            rms_eps=float(c["rms_norm_eps"]),
            rope_theta=float(c["rope_theta"]),
            yarn=(float(r["factor"]), int(r["original_max_position_embeddings"]),
                  float(r["beta_fast"]), float(r["beta_slow"]),
                  float(r["mscale"]), float(r["mscale_all_dim"])))


def _m(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(s: Spec) -> float:
    factor, *_, mscale_all = s.yarn
    return (s.nope + s.rope) ** -0.5 * _m(factor, mscale_all) ** 2


def inv_freq(s: Spec) -> np.ndarray:
    """YaRN's inverse frequencies of the rope dims (float64, on the host)."""
    factor, orig, fast, slow, _, _ = s.yarn
    d, base = s.rope, s.rope_theta
    extra = 1.0 / base ** (np.arange(0, d, 2) / d)
    inter = extra / factor

    def dim(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    lo, hi = max(math.floor(dim(fast)), 0), min(math.ceil(dim(slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - lo) / ((hi - lo) or 0.001), 0, 1)
    return inter * ramp + extra * (1 - ramp)


def check_program(cfg, spec: Spec) -> None:
    """Raise unless the program's ``ModelConfig`` serves this DeepSeek-V2
    configuration and expert share."""
    y = cfg.rope_yarn
    got = (cfg.family, cfg.mlp_type, cfg.window, cfg.tie_embeddings,
           cfg.n_layers, cfg.first_dense_layers, cfg.d_model, cfg.n_heads,
           cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.qk_rope_dim,
           cfg.v_head_dim, cfg.dense_d_ff, cfg.d_ff, cfg.n_experts,
           cfg.experts_held, cfg.first_expert, cfg.top_k,
           cfg.n_shared_experts, cfg.norm_topk, cfg.vocab, cfg.rope_theta,
           None if y is None else (y.factor, y.original_max, y.beta_fast,
                                   y.beta_slow, y.mscale, y.mscale_all_dim))
    want = ("moe", "swiglu", None, False, spec.layers, spec.dense_layers,
            spec.d_model, spec.heads, spec.kv_rank, spec.nope, spec.rope,
            spec.v_dim, spec.dense_ff, spec.expert_ff, spec.experts,
            spec.experts_held, spec.first_expert, spec.top_k, spec.shared,
            spec.norm_topk, spec.vocab, spec.rope_theta, spec.yarn)
    if got != want:
        raise ValueError(f"program config {cfg.name!r} gives {got}, the "
                         f"configuration file states {want}")


# ------------------------------------------------------------------ weights
def _attn(ks, s: Spec, dtype) -> dict:
    d, h, r = s.d_model, s.heads, s.kv_rank
    return {"wq": W._grid(ks[0], (d, h, s.nope + s.rope), d ** -0.5, dtype),
            "wkv_a": W._grid(ks[1], (d, r), d ** -0.5, dtype),
            "wk_rope": W._grid(ks[2], (d, s.rope), d ** -0.5, dtype),
            "kv_norm": W._gain(ks[3], r, dtype),
            "wk_b": W._grid(ks[4], (r, h, s.nope), r ** -0.5, dtype),
            "wv_b": W._grid(ks[5], (r, h, s.v_dim), r ** -0.5, dtype),
            "wo": W._grid(ks[6], (h, s.v_dim, d), (h * s.v_dim) ** -0.5,
                          dtype)}


def _swiglu(ks, d: int, f: int, dtype) -> dict:
    return {"w_gate": W._grid(ks[0], (d, f), d ** -0.5, dtype),
            "w_up": W._grid(ks[1], (d, f), d ** -0.5, dtype),
            "w_down": W._grid(ks[2], (f, d), f ** -0.5, dtype)}


def layer(key, l, s: Spec, dtype, moe: bool) -> dict:
    """Layer ``l``'s weights (``l`` may be traced): the dense FFN, or the
    router, the held experts and the shared experts."""
    k = jax.random.fold_in(jax.random.fold_in(key, W._LAYER), l)
    ks = [jax.random.fold_in(k, i) for i in range(16)]
    out = {"ln1": W._gain(ks[7], s.d_model, dtype), "attn": _attn(ks, s, dtype),
           "ln2": W._gain(ks[8], s.d_model, dtype)}
    if not moe:
        out["ffn"] = _swiglu(ks[9:12], s.d_model, s.dense_ff, dtype)
        return out
    # each expert's weights from its published id, whichever share holds it
    ffn = jax.vmap(lambda e: _swiglu(
        [jax.random.fold_in(x, e) for x in ks[9:12]], s.d_model, s.expert_ff,
        dtype))(s.first_expert + jnp.arange(s.experts_held))
    ffn["router"] = W._grid(ks[12], (s.d_model, s.experts),
                            s.d_model ** -0.5, dtype)
    ffn["shared"] = _swiglu(ks[13:16], s.d_model, s.shared * s.expert_ff,
                            dtype)
    out["ffn"] = ffn
    return out


def top(key, s: Spec, dtype) -> dict:
    k = jax.random.fold_in(key, W._TOP)
    ks = [jax.random.fold_in(k, i) for i in range(3)]
    return {"embed": W._grid(ks[0], (s.vocab, s.d_model), 1.0, dtype),
            "ln_f": W._gain(ks[1], s.d_model, dtype),
            "lm_head": W._grid(ks[2], (s.d_model, s.vocab),
                               s.d_model ** -0.5, dtype)}


def program_params(key, s: Spec, dtype) -> dict:
    """All weights in the serving layout: the dense stack, then the MoE
    stack, each made one layer at a time (``lax.map``) so that making them
    holds one layer's temporaries."""
    out = top(key, s, dtype)
    out["dense_blocks"] = jax.lax.map(
        lambda l: layer(key, l, s, dtype, moe=False),
        jnp.arange(s.dense_layers))
    out["blocks"] = jax.lax.map(
        lambda l: layer(key, l, s, dtype, moe=True),
        jnp.arange(s.dense_layers, s.layers))
    return out


def make(seed: int, s: Spec, dtype):
    """The served weights, made on the device in one jitted call."""
    return jax.jit(lambda k: program_params(k, s, dtype))(W.seed_key(seed))


# ------------------------------------------------------------------- counts
def _attn_params(s: Spec) -> int:
    """Matmul parameters a token meets in one layer's attention in the
    absorbed form the program serves: q, the latent and rope key, q_nope
    into the latent, the latent output out of it, and o."""
    d, h, r = s.d_model, s.heads, s.kv_rank
    return (d * h * (s.nope + s.rope) + d * (r + s.rope) + h * s.nope * r
            + h * r * s.v_dim + h * s.v_dim * d)


def _token_matmul_flops(s: Spec) -> float:
    """One token through every layer's projections: the dense layers'
    SwiGLU, and in each MoE layer the router, the expected
    ``top_k * held / experts`` held experts and the shared experts."""
    d = s.d_model
    moe = (d * s.experts
           + s.top_k * s.experts_held / s.experts * 3 * d * s.expert_ff
           + 3 * d * s.shared * s.expert_ff)
    per = (s.layers * _attn_params(s) + s.dense_layers * 3 * d * s.dense_ff
           + s.moe_layers * moe)
    return 2.0 * per


def _attend_flops(s: Spec, keys: float) -> float:
    """Absorbed attention over ``keys`` cached positions, every layer: the
    scores against the latent and the rope key, and the latent output."""
    return 2.0 * s.heads * keys * (2 * s.kv_rank + s.rope) * s.layers


def token_flops(s: Spec, ctx: int, head: bool = True) -> float:
    """One token decoded at context ``ctx`` (itself included)."""
    f = _token_matmul_flops(s) + _attend_flops(s, ctx)
    return f + (2.0 * s.d_model * s.vocab if head else 0.0)


def chunk_flops(s: Spec, start: int, n: int, last: bool) -> float:
    """A prefill chunk of ``n`` real tokens at positions start..start+n-1,
    in the absorbed form the program's chunks use; the output head only for
    the prompt's final token."""
    keys = n * start + n * (n + 1) // 2
    f = _token_matmul_flops(s) * n + _attend_flops(s, keys)
    return f + (2.0 * s.d_model * s.vocab if last else 0.0)


def kv_read_bytes(s: Spec, ctx: int, page_size: int) -> float:
    """Cache bytes attention for one row at context ``ctx`` must read: the
    latent and the rope key of each of its ``ceil(ctx / page_size)`` live
    pages, every layer, once."""
    pages = -(-ctx // page_size)
    return (float(s.layers) * pages * page_size * (s.kv_rank + s.rope)
            * KV_ITEMSIZE)


# ---------------------------------------------------------------- reference
def _mm(eq, a, b, quant):
    return jnp.einsum(eq, _q(a, quant), _q(b, quant), precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, freqs, mult):
    """x: (S, H, D) at positions ``pos`` (S,): de-interleave the dims, then
    ``x cos + rotate_half(x) sin``."""
    s, h, d = x.shape
    x = x.reshape(s, h, d // 2, 2).swapaxes(-1, -2).reshape(s, h, d)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    emb = jnp.concatenate([ang, ang], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * jnp.cos(emb) * mult + rot * jnp.sin(emb) * mult


def _attention(h, a, spec: Spec, quant):
    """One sequence's MLA, keys and values expanded per head, queries in
    blocks of :data:`BLOCK`."""
    f32 = lambda t: t.astype(jnp.float32)
    factor, *_, mscale, mscale_all = spec.yarn
    freqs = jnp.asarray(inv_freq(spec), jnp.float32)
    mult = _m(factor, mscale) / _m(factor, mscale_all)
    s = h.shape[0]
    pos = jnp.arange(s)
    c = _rms(_mm("sd,dr->sr", h, f32(a["wkv_a"]), quant), f32(a["kv_norm"]),
             spec.rms_eps)
    k_pe = _rope(_mm("sd,dp->sp", h, f32(a["wk_rope"]), quant)[:, None],
                 pos, freqs, mult)
    k_nope = _mm("sr,rhn->shn", c, f32(a["wk_b"]), quant)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:2] + (spec.rope,))], -1)
    v = _mm("sr,rhv->shv", c, f32(a["wv_b"]), quant)
    scale = softmax_scale(spec)

    def block(i):
        rows = i * BLOCK + jnp.arange(BLOCK)
        q = _mm("sd,dhk->shk", jax.lax.dynamic_slice_in_dim(h, i * BLOCK,
                                                             BLOCK),
                f32(a["wq"]), quant)
        q = jnp.concatenate(
            [q[..., :spec.nope], _rope(q[..., spec.nope:], rows, freqs,
                                       mult)], -1)
        sc = jnp.einsum("qhk,thk->hqt", q, k, precision=HI) * scale
        sc = jnp.where(rows[:, None] >= pos[None, :], sc, -jnp.inf)
        o = jnp.einsum("hqt,thv->qhv", jax.nn.softmax(sc, axis=-1), v,
                       precision=HI)
        return _mm("shv,hvd->sd", o, f32(a["wo"]), quant)

    return jax.lax.map(block, jnp.arange(s // BLOCK)).reshape(s, -1)


def _swiglu_apply(h, w, quant):
    f32 = lambda t: t.astype(jnp.float32)
    u = (jax.nn.silu(_mm("sd,df->sf", h, f32(w["w_gate"]), quant))
         * _mm("sd,df->sf", h, f32(w["w_up"]), quant))
    return _mm("sf,fd->sd", u, f32(w["w_down"]), quant)


def _experts(h, ff, spec: Spec, quant):
    """The held experts' gated sum plus the shared experts."""
    probs = jax.nn.softmax(_mm("sd,de->se", h, ff["router"].astype(
        jnp.float32), quant), axis=-1)
    top, idx = jax.lax.top_k(probs, spec.top_k)
    if spec.norm_topk:
        top = top / jnp.sum(top, -1, keepdims=True)
    held = spec.first_expert + jnp.arange(spec.experts_held)
    gates = jnp.sum(jnp.where(idx[:, :, None] == held, top[:, :, None], 0.0),
                    axis=1)                                 # (S, held)

    def one(y, e):
        w = jax.tree.map(lambda t: t[e], {k: ff[k] for k in
                                          ("w_gate", "w_up", "w_down")})
        return y + gates[:, e, None] * _swiglu_apply(h, w, quant), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(spec.experts_held))
    return y + _swiglu_apply(h, ff["shared"], quant)


@functools.partial(jax.jit, static_argnames=("spec", "quant", "moe"))
def _block(x, w, *, spec: Spec, quant, moe: bool):
    """One sequence (S, d) through one layer; the FFN in token blocks."""
    f32 = lambda a: a.astype(jnp.float32)
    x = x + _attention(_rms(x, f32(w["ln1"]), spec.rms_eps), w["attn"],
                       spec, quant)
    h = _rms(x, f32(w["ln2"]), spec.rms_eps)
    ffn = (lambda hb: _experts(hb, w["ffn"], spec, quant)) if moe else \
        (lambda hb: _swiglu_apply(hb, w["ffn"], quant))
    s, d = h.shape
    return x + jax.lax.map(ffn, h.reshape(s // BLOCK, BLOCK, d)).reshape(s, d)


@functools.partial(jax.jit, static_argnames=("spec", "moe"))
def _layer_weights(key, l, *, spec: Spec, moe: bool):
    return layer(key, l, spec, jnp.bfloat16, moe)


@functools.partial(jax.jit, static_argnames=("spec",))
def _top_weights(key, *, spec: Spec):
    return top(key, spec, jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("spec", "quant"))
def _head(x, rows, t, *, spec: Spec, quant):
    h = jnp.take_along_axis(x, rows[..., None], axis=1)
    h = _rms(h, t["ln_f"].astype(jnp.float32), spec.rms_eps)
    return _mm("brd,dv->brv", h, t["lm_head"].astype(jnp.float32), quant)


def logits(seed: int, spec: Spec, seqs, reads, *, pad_to: int,
           quant: str | None = None) -> list[np.ndarray]:
    """Float32 logits of each sequence at its read positions.

    ``seqs``: token arrays, each at most ``pad_to`` long; ``reads``: for
    each, the positions whose next-token logits are wanted.  The sample is
    padded to its longest sequence rounded up to a query block.  Returns
    one (len(reads[i]), vocab) array per sequence, on the host."""
    if any(len(s) > pad_to for s in seqs):
        raise ValueError(f"a sequence is longer than pad_to={pad_to}")
    key = W.seed_key(seed)
    n = -(-max(len(s) for s in seqs) // BLOCK) * BLOCK
    toks = np.zeros((len(seqs), n), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    r = max(len(x) for x in reads)
    rows = np.zeros((len(seqs), r), np.int32)
    for i, x in enumerate(reads):
        rows[i, :len(x)] = x
        rows[i, len(x):] = x[-1] if len(x) else 0
    t = _top_weights(key, spec=spec)
    xs = [t["embed"].astype(jnp.float32)[jnp.asarray(s)] for s in toks]
    del t
    for l in range(spec.layers):
        moe = l >= spec.dense_layers
        w = _layer_weights(key, l, spec=spec, moe=moe)
        xs = [_block(x, w, spec=spec, quant=quant, moe=moe) for x in xs]
    out = np.asarray(_head(jnp.stack(xs), jnp.asarray(rows),
                           _top_weights(key, spec=spec), spec=spec,
                           quant=quant))
    return [out[i, :len(x)] for i, x in enumerate(reads)]
