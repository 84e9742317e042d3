"""Block compositions: dense/MoE decoder blocks, Mamba blocks, Zamba-style
hybrid groups, and encoder blocks.  All block applies are scan-compatible
(uniform aux structure) and support train / prefill / decode modes."""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as attn_mod
from repro.models import mlp as mlp_mod
from repro.models import moe as moe_mod
from repro.models import modules as nn
from repro.models import ssm as ssm_mod
from repro.models.config import ModelConfig

ZERO_AUX = lambda: {"load_balance": jnp.float32(0.0), "router_z": jnp.float32(0.0)}


# ------------------------------------------------------------ decoder block
def init_decoder_block(key, cfg: ModelConfig, cross: bool = False):
    ks = jax.random.split(key, 6)
    p = {
        "ln1": nn.rmsnorm_init(ks[0], cfg.d_model),
        "attn": attn_mod.init_attention(ks[1], cfg),
        "ln2": nn.rmsnorm_init(ks[2], cfg.d_model),
    }
    if cfg.family == "moe":
        p["ffn"] = moe_mod.init_moe(ks[3], cfg)
    else:
        p["ffn"] = mlp_mod.init_mlp(ks[3], cfg)
    if cross:
        p["ln_x"] = nn.rmsnorm_init(ks[4], cfg.d_model)
        p["xattn"] = attn_mod.init_attention(ks[5], cfg, cross=True)
    return p


def _ffn(p, x, cfg: ModelConfig, dropless: bool = False, valid=None):
    if cfg.family == "moe":
        return moe_mod.moe(p["ffn"], x, cfg, dropless=dropless, valid=valid)
    return mlp_mod.mlp(p["ffn"], x, cfg), ZERO_AUX()


def decoder_block(p, x, cfg: ModelConfig, *, causal: bool = True,
                  pos_offset: int | jnp.ndarray = 0,
                  cache: dict[str, Any] | None = None,
                  return_cache: bool = False,
                  cross_kv: tuple | None = None,
                  valid: jnp.ndarray | None = None):
    """Pre-norm attention then FFN -> (x, aux, cache).  ``valid`` (B, S)
    marks the real tokens (the expert share computes and counts only
    theirs)."""
    h = nn.rmsnorm_apply(p["ln1"], x)
    if cache is not None or return_cache:
        a, new_cache = attn_mod.attention(p["attn"], h, cfg, causal=causal,
                                          pos_offset=pos_offset, cache=cache,
                                          return_cache=return_cache)
    else:
        a = attn_mod.attention(p["attn"], h, cfg, causal=causal,
                               pos_offset=pos_offset)
        new_cache = None
    x = x + a
    if cross_kv is not None:
        hx = nn.rmsnorm_apply(p["ln_x"], x)
        x = x + attn_mod.cross_attention(p["xattn"], hx, cross_kv, cfg)
    h2 = nn.rmsnorm_apply(p["ln2"], x)
    y, aux = _ffn(p, h2, cfg, dropless=cache is not None or return_cache,
                  valid=valid)
    return x + y, aux, new_cache


# ------------------------------------------------------------- mamba block
def init_mamba_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    return {"ln": nn.rmsnorm_init(ks[0], cfg.d_model),
            "mixer": ssm_mod.init_mamba(ks[1], cfg)}


def mamba_block(p, x, cfg: ModelConfig, *, state=None, return_state=False):
    h = nn.rmsnorm_apply(p["ln"], x)
    if state is not None or return_state:
        y, new_state = ssm_mod.mamba(p["mixer"], h, cfg, state=state,
                                     return_state=True)
        return x + y, new_state
    return x + ssm_mod.mamba(p["mixer"], h, cfg), None


# ------------------------------------------------------- hybrid (Zamba-2)
def init_hybrid_group(key, cfg: ModelConfig):
    """One scan group: ``hybrid_group`` mamba blocks.  The shared attention
    block's params live OUTSIDE the scan (one copy reused by all groups)."""
    return nn.stack_layers(lambda k: init_mamba_block(k, cfg), key,
                           cfg.hybrid_group)


def hybrid_group(gp, shared, x, cfg: ModelConfig, apply_attn: jnp.ndarray, *,
                 states=None, attn_cache=None, return_state=False,
                 pos_offset=0):
    """gp: stacked mamba-block params (g, ...); shared: shared attn block
    params; apply_attn: traced bool — whether this group runs the shared
    attention block (Zamba-2 applies it periodically)."""
    new_states = []
    for i in range(cfg.hybrid_group):
        pi = jax.tree.map(lambda a: a[i], gp)
        st = None if states is None else jax.tree.map(lambda a: a[i], states)
        x, ns = mamba_block(pi, x, cfg, state=st,
                            return_state=return_state or states is not None)
        if ns is not None:
            new_states.append(ns)

    want_cache = attn_cache is not None or return_state

    def with_attn(args):
        x, cache = args
        out, _, new_cache = decoder_block(shared, x, cfg, causal=True,
                                          pos_offset=pos_offset, cache=cache,
                                          return_cache=return_state)
        if new_cache is None:
            new_cache = cache
        return out, new_cache

    def without(args):
        x, cache = args
        return x, cache

    if want_cache:
        if attn_cache is None:   # prefill: must materialize cache either way
            x2, new_cache = with_attn((x, None))
            x = jnp.where(apply_attn, x2, x)
        else:
            x, new_cache = jax.lax.cond(apply_attn, with_attn, without,
                                        (x, attn_cache))
    else:
        x = jax.lax.cond(apply_attn,
                         lambda v: decoder_block(shared, v, cfg, causal=True,
                                                 pos_offset=pos_offset)[0],
                         lambda v: v, x)
        new_cache = None
    stacked_states = (jax.tree.map(lambda *a: jnp.stack(a), *new_states)
                      if new_states else None)
    return x, stacked_states, new_cache
