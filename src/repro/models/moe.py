"""Top-k MoE with sort-based capacity dispatch (expert-parallel over 'model').

Dispatch is the production-standard permute/bucket scheme (MaxText/MegaBlocks
lineage, without a custom grouped-GEMM kernel):

  1. router logits -> top-k experts per token (softmax-renormalized gates);
  2. token copies sorted by expert id; position-within-expert computed from
     the sorted segment starts; copies beyond expert capacity are dropped;
  3. scatter into a dense (E, C, d) buffer; per-expert FFN as one batched
     einsum with experts sharded over the 'model' axis (EP);
  4. gather back, unsort, gate-weight, sum the k copies.

Two dispatch scopes for training's capacity-bounded dispatch:

  * global (``cfg.moe_groups == 0``): one argsort/scatter over all tokens.
    Simple, but under GSPMD the scatter into the expert buffer partial-sums
    across data shards — it all-reduces the whole (E, C, d) buffer every
    layer (measured in EXPERIMENTS.md §Perf: the dominant collective for
    dbrx/llama4).
  * group-local (``cfg.moe_groups = G``): tokens are grouped along the batch
    dim (groups sharded over pod x data) and routed within their group, so
    sort/scatter are shard-local and the expert einsum
    ``gecd,edf->gecf`` is already aligned on (G->data, E->model) — no
    dispatch collective at all.  This is the EP-friendly layout GShard-style
    systems use.

Serving (``dropless``) and every training step of an expert share
(``cfg.experts_held > 0``, ROADMAP B3) take the share path instead: the
device holds routed experts [first_expert, first_expert + held) (every
expert unless ``experts_held`` says otherwise), routes over all
``n_experts`` at their published width, and computes only its own experts'
part for the tokens routed to them — dropless, the routed rows sorted by
held expert through grouped matmuls (``jax.lax.ragged_dot``), with no
(E, capacity, d) buffer.  What absent experts would add is left out.
Shared experts (``n_shared_experts``) are one SwiGLU every token takes,
added on every device alike.

Aux losses: switch-style load balancing + router z-loss; the share path
also reports ``moe_local_rows`` / ``moe_rows``, the (token, expert) pairs
routed to the held experts and in all.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.dist.partition import shard
from repro.dist.tp import tp_allreduce
from repro.models import mlp as mlp_mod
from repro.models import modules as nn
from repro.models.config import ModelConfig

#: the aux entries that are losses (the rest are counts)
LOSSES = ("load_balance", "router_z")


def shared_cfg(cfg: ModelConfig) -> ModelConfig:
    """The shared experts as one dense MLP of their summed width."""
    return dataclasses.replace(cfg, d_ff=cfg.n_shared_experts * cfg.d_ff)


def init_moe(key, cfg: ModelConfig):
    d, f = cfg.d_model, cfg.d_ff
    e = cfg.held_experts
    ks = jax.random.split(key, 5)
    p = {
        "router": nn.param(ks[0], (d, cfg.n_experts), ("embed", "experts"),
                           scale=d ** -0.5),
        "w_gate": nn.param(ks[1], (e, d, f), ("experts", "embed", "mlp"),
                           scale=d ** -0.5),
        "w_up": nn.param(ks[2], (e, d, f), ("experts", "embed", "mlp"),
                         scale=d ** -0.5),
        "w_down": nn.param(ks[3], (e, f, d), ("experts", "mlp", "embed"),
                           scale=f ** -0.5),
    }
    if cfg.n_shared_experts:
        p["shared"] = mlp_mod.init_mlp(ks[4], shared_cfg(cfg))
    return p


def _route(p, xt: jnp.ndarray, cfg: ModelConfig):
    """xt: (T, d) -> (gates (T,k), expert_idx (T,k), aux)."""
    e, k = cfg.n_experts, cfg.top_k
    logits = (xt @ p["router"].astype(jnp.float32)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)
    if cfg.norm_topk:
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    density = jnp.mean(jax.nn.one_hot(expert_idx[:, 0], e), axis=0)
    mean_probs = jnp.mean(probs, axis=0)
    aux = {
        "load_balance": cfg.router_aux_weight * e * jnp.sum(density * mean_probs),
        "router_z": cfg.router_z_weight *
                    jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2),
    }
    return gate_vals, expert_idx, aux


def _dispatch_ffn(p, xt: jnp.ndarray, gate_vals, expert_idx,
                  cfg: ModelConfig, cap: int) -> jnp.ndarray:
    """Sort-based capacity dispatch + per-expert FFN over (T, d) tokens."""
    t, d = xt.shape
    e, k = cfg.n_experts, cfg.top_k
    flat_expert = expert_idx.reshape(-1)                        # (T*k,)
    flat_gate = gate_vals.reshape(-1)
    flat_token = jnp.repeat(jnp.arange(t), k)

    sort_idx = jnp.argsort(flat_expert, stable=True)
    sorted_expert = flat_expert[sort_idx]
    sorted_token = flat_token[sort_idx]
    seg_start = jnp.searchsorted(sorted_expert, jnp.arange(e))
    pos_in_exp = jnp.arange(t * k) - seg_start[sorted_expert]
    keep = pos_in_exp < cap
    dest = jnp.where(keep, sorted_expert * cap + pos_in_exp, e * cap)

    buf = jnp.zeros((e * cap + 1, d), xt.dtype).at[dest].set(xt[sorted_token])
    buf = buf[:-1].reshape(e, cap, d)

    dt = xt.dtype
    if cfg.mlp_type in ("swiglu", "geglu"):
        h = _act(cfg)(jnp.einsum("ecd,edf->ecf", buf,
                                 p["w_gate"].astype(dt))) * \
            jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(dt))
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(dt)),
                        approximate=True)
    # manual-TP seam: under serving TP the experts replicate and the FFN
    # hidden dim f shards, so the down projection is a partial sum per
    # shard (identity outside a tp_context; GSPMD EP is unaffected)
    out_buf = tp_allreduce(jnp.einsum("ecf,efd->ecd", h,
                                      p["w_down"].astype(dt)))

    gathered = out_buf.reshape(e * cap, d)[jnp.minimum(dest, e * cap - 1)]
    gathered = jnp.where(keep[:, None], gathered, 0.0)
    contrib = gathered * flat_gate[sort_idx][:, None].astype(dt)
    return jnp.zeros((t, d), dt).at[sorted_token].add(contrib)


def _act(cfg: ModelConfig):
    return jax.nn.silu if cfg.mlp_type == "swiglu" else \
        (lambda v: jax.nn.gelu(v, approximate=True))


def _share_ffn(p, xt: jnp.ndarray, gate_vals, expert_idx, cfg: ModelConfig,
               valid: jnp.ndarray | None):
    """The held experts' part over (T, d) tokens -> (y, local rows).

    The (token, expert) pairs routed to held experts (and, where ``valid``
    (T,) is given, from valid tokens) are sorted by held expert; the pairs
    of absent experts sort after them, outside every group, and
    ``ragged_dot`` computes no row for them."""
    t, d = xt.shape
    k, held = cfg.top_k, cfg.held_experts
    local = expert_idx.reshape(-1) - cfg.first_expert         # (T*k,)
    mine = (local >= 0) & (local < held)
    if valid is not None:
        mine &= jnp.repeat(valid, k)
    key = jnp.where(mine, local, held)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=held + 1)[:held].astype(jnp.int32)
    token = order // k
    xs = xt[token]
    dt = xt.dtype
    if cfg.mlp_type in ("swiglu", "geglu"):
        h = _act(cfg)(jax.lax.ragged_dot(xs, p["w_gate"].astype(dt), sizes)) \
            * jax.lax.ragged_dot(xs, p["w_up"].astype(dt), sizes)
    else:
        h = jax.nn.gelu(jax.lax.ragged_dot(xs, p["w_up"].astype(dt), sizes),
                        approximate=True)
    # manual-TP seam, as in _dispatch_ffn
    out = tp_allreduce(jax.lax.ragged_dot(h, p["w_down"].astype(dt), sizes))
    gate = gate_vals.reshape(-1)[order].astype(dt)
    out = jnp.where(mine[order][:, None], out * gate[:, None], 0.0)
    return jnp.zeros((t, d), dt).at[token].add(out), jnp.sum(mine)


def moe(p, x: jnp.ndarray, cfg: ModelConfig, *, dropless: bool = False,
        valid: jnp.ndarray | None = None):
    """x: (B, S, d) -> (y, aux dict).

    ``dropless=True`` (the serving paths) and an expert share compute the
    held experts' routed rows alone, dropping none (:func:`_share_ffn`);
    ``valid`` (B, S) then marks the tokens whose rows are computed and
    counted (padding and idle decode rows are left out).  Training without
    a share takes the capacity-bounded dispatch."""
    b, s, d = x.shape
    with jax.named_scope("moe"):
        if dropless or cfg.experts_held:
            t = b * s
            xt = x.reshape(t, d)
            gate_vals, expert_idx, aux = _route(p, xt, cfg)
            vt = None if valid is None else valid.reshape(t)
            y, local = _share_ffn(p, xt, gate_vals, expert_idx, cfg, vt)
            y = y.reshape(b, s, d)
            rows = cfg.top_k * (t if vt is None else jnp.sum(vt))
            aux = dict(aux, moe_local_rows=local.astype(jnp.int32),
                       moe_rows=jnp.asarray(rows, jnp.int32))
        else:
            y, aux = _capacity_moe(p, x, cfg)
        if cfg.n_shared_experts:
            y = y + mlp_mod.mlp(p["shared"], x, shared_cfg(cfg))
    return y, aux


def _capacity_moe(p, x: jnp.ndarray, cfg: ModelConfig):
    """Every expert over capacity-bounded (E, cap, d) buffers, in one
    dispatch scope or ``cfg.moe_groups`` of them."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    g = cfg.moe_groups
    if g and t % g == 0 and t // g >= 1:
        tg = t // g
        xg = x.reshape(g, tg, d)
        xg = shard(xg, "batch", None, None)       # groups over pod x data
        gate_vals, expert_idx, aux = jax.vmap(
            lambda xt: _route(p, xt, cfg))(xg)
        aux = {kk: jnp.mean(v) for kk, v in aux.items()}
        cap = min(max(int(tg * k / e * cfg.capacity_factor), 1), tg)
        y = jax.vmap(lambda xt, gv, ei:
                     _dispatch_ffn(p, xt, gv, ei, cfg, cap))(
            xg, gate_vals, expert_idx)
        y = shard(y, "batch", None, None)
        return y.reshape(b, s, d), aux

    xt = x.reshape(t, d)
    gate_vals, expert_idx, aux = _route(p, xt, cfg)
    cap = min(max(int(t * k / e * cfg.capacity_factor), 1), t)
    y = _dispatch_ffn(p, xt, gate_vals, expert_idx, cfg, cap)
    return y.reshape(b, s, d), aux
