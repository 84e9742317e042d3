"""Top-level models: decoder LMs (dense/MoE/VLM), Mamba-2, Zamba-2 hybrid,
and the encoder-decoder (audio) — with train, prefill and decode entry points.

Layer stacks run under ``jax.lax.scan`` over stacked per-layer params with
optional remat — O(1) HLO size in depth (what makes the 80-compile dry-run
feasible) and the production-standard choice at 1000+-node scale.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.dist.partition import shard
from repro.models import attention as attn_mod
from repro.models import blocks
from repro.models import modules as nn
from repro.models.config import ModelConfig
from repro.models.moe import LOSSES

Params = Any


def layer_stacks(cfg: ModelConfig):
    """(params key, config, first layer, layer count) of each scanned
    decoder stack, in order: the leading dense SwiGLU layers of width
    ``dense_d_ff`` (DeepSeek's ``first_k_dense_replace``), then the rest."""
    n = cfg.first_dense_layers
    if not n:
        return [("blocks", cfg, 0, cfg.n_layers)]
    dense = dataclasses.replace(cfg, family="dense", d_ff=cfg.dense_d_ff,
                                first_dense_layers=0)
    return [("dense_blocks", dense, 0, n),
            ("blocks", cfg, n, cfg.n_layers - n)]


# ===================================================================== init
def init_lm(key, cfg: ModelConfig) -> Params:
    cfg.validate()
    ks = jax.random.split(key, 10)
    d = cfg.d_model
    p: dict[str, Any] = {}
    if cfg.family != "enc_dec":
        p["embed"] = nn.param(ks[0], (cfg.vocab, d), ("vocab", "embed"),
                              scale=1.0)
        p["ln_f"] = nn.rmsnorm_init(ks[1], d)
        p["lm_head"] = nn.param(ks[2], (d, cfg.vocab), ("embed", "vocab"),
                                scale=d ** -0.5)
    if cfg.family in ("dense", "moe", "vlm"):
        for (name, c, _, n), k in zip(layer_stacks(cfg), (ks[3], ks[9])):
            p[name] = nn.stack_layers(
                lambda k, c=c: blocks.init_decoder_block(k, c), k, n)
    elif cfg.family == "ssm":
        p["blocks"] = nn.stack_layers(
            lambda k: blocks.init_mamba_block(k, cfg), ks[3], cfg.n_layers)
    elif cfg.family == "hybrid":
        n_groups = cfg.n_layers // cfg.hybrid_group
        trailing = cfg.n_layers % cfg.hybrid_group
        p["groups"] = nn.stack_layers(
            lambda k: blocks.init_hybrid_group(k, cfg), ks[3], n_groups)
        p["shared_attn"] = blocks.init_decoder_block(ks[4], cfg)
        if trailing:
            p["trailing"] = nn.stack_layers(
                lambda k: blocks.init_mamba_block(k, cfg), ks[5], trailing)
    elif cfg.family == "enc_dec":
        p["enc_blocks"] = nn.stack_layers(
            lambda k: blocks.init_decoder_block(k, cfg), ks[3], cfg.enc_layers)
        p["enc_ln"] = nn.rmsnorm_init(ks[4], d)
        p["dec_embed"] = nn.param(ks[5], (cfg.vocab, d), ("vocab", "embed"),
                                  scale=1.0)
        p["dec_blocks"] = nn.stack_layers(
            lambda k: blocks.init_decoder_block(k, cfg, cross=True), ks[6],
            cfg.dec_layers)
        p["dec_ln"] = nn.rmsnorm_init(ks[7], d)
        p["lm_head"] = nn.param(ks[8], (d, cfg.vocab), ("embed", "vocab"),
                                scale=d ** -0.5)
    else:
        raise ValueError(cfg.family)
    if cfg.param_dtype != "float32":
        pd = jnp.dtype(cfg.param_dtype)
        p = jax.tree.map(
            lambda prm: nn.Param(prm.value.astype(pd), prm.axes)
            if jnp.issubdtype(prm.value.dtype, jnp.floating) else prm,
            p, is_leaf=nn.is_param)
    return p


def init_lm_shapes(key, cfg: ModelConfig):
    """Shape-only init (no allocation) — dry-run entry point."""
    return jax.eval_shape(functools.partial(init_lm, cfg=cfg), key)


def param_logical_axes(cfg: ModelConfig):
    """Logical partition axes for every param leaf, recovered without
    allocating (``nn.Param`` carries its axes through ``eval_shape``).  Lets
    sharded serving derive param shardings from a plain (unwrapped) param
    tree — the tree structure matches ``nn.unwrap(init_lm(...))``."""
    return nn.axes_of(init_lm_shapes(jax.random.PRNGKey(0), cfg))


# =============================================================== scan utils
def _maybe_remat(fn, cfg: ModelConfig):
    if not cfg.remat or cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        # save matmul outputs, recompute the cheap elementwise ops only —
        # trades activation memory for a large cut in recompute FLOPs/bytes
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.checkpoint_dots)
    return jax.checkpoint(fn)


def _scan(fn, carry, xs, cfg: ModelConfig):
    if cfg.scan_layers:
        return jax.lax.scan(_maybe_remat(fn, cfg), carry, xs)
    f = _maybe_remat(fn, cfg)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        carry, y = f(carry, jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    stacked = (jax.tree.map(lambda *a: jnp.stack(a), *ys)
               if ys and ys[0] is not None else None)
    return carry, stacked


def _sum_aux(aux):
    return {k: jnp.sum(v) for k, v in aux.items()} if aux else {}


def _add_aux(a: dict, b: dict) -> dict:
    return {k: a.get(k, 0) + b.get(k, 0) for k in {**a, **b}}


def _counts(aux: dict) -> dict:
    """The aux entries that count work rather than add to the loss."""
    return {k: v for k, v in aux.items() if k not in LOSSES}


def _split_layers(tree, stacks):
    """Split a per-layer tree's leading axis into one part per stack."""
    if len(stacks) == 1:
        return [tree]
    return [jax.tree.map(lambda a, s=s, n=n: a[s:s + n], tree)
            for _, _, s, n in stacks]


def _concat_layers(parts):
    if len(parts) == 1:
        return parts[0]
    return jax.tree.map(lambda *a: jnp.concatenate(a), *parts)


# ============================================================== forward (train)
def embed_inputs(p, inputs: dict[str, jnp.ndarray], cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)
    if cfg.input_mode == "embeddings" and "embeds" in inputs:
        x = inputs["embeds"].astype(dt)
    else:
        x = p["embed"].astype(dt)[inputs["tokens"]]
    return shard(x, "batch", "act_seq" if cfg.seq_shard else "seq", None)


def forward(p: Params, inputs: dict[str, jnp.ndarray], cfg: ModelConfig):
    """Training/eval forward -> (logits, aux).  Decoder families."""
    if cfg.family == "enc_dec":
        return _forward_enc_dec(p, inputs, cfg)
    x = embed_inputs(p, inputs, cfg)

    if cfg.family in ("dense", "moe", "vlm"):
        aux = {}
        for name, c, _, _ in layer_stacks(cfg):
            def body(h, lp, c=c):
                h, a, _ = blocks.decoder_block(lp, h, c, causal=True)
                return h, a
            x, a = _scan(body, x, p[name], cfg)
            aux = _add_aux(aux, _sum_aux(a))
    elif cfg.family == "ssm":
        def body(h, lp):
            h, _ = blocks.mamba_block(lp, h, cfg)
            return h, blocks.ZERO_AUX()
        x, aux = _scan(body, x, p["blocks"], cfg)
        aux = _sum_aux(aux)
    elif cfg.family == "hybrid":
        x, aux = _hybrid_forward(p, x, cfg)
    else:
        raise ValueError(cfg.family)

    x = nn.rmsnorm_apply(p["ln_f"], x)
    logits = x @ p["lm_head"].astype(x.dtype)
    logits = shard(logits, "batch", "seq", "vocab")
    return logits, aux


def _hybrid_forward(p, x, cfg: ModelConfig):
    n_groups = cfg.n_layers // cfg.hybrid_group
    flags = _hybrid_flags(cfg, n_groups)

    def body(h, xs):
        gp, flag = xs
        h, _, _ = blocks.hybrid_group(gp, p["shared_attn"], h, cfg, flag)
        return h, blocks.ZERO_AUX()

    x, aux = _scan(body, x, (p["groups"], flags), cfg)
    if "trailing" in p:
        def tbody(h, lp):
            h, _ = blocks.mamba_block(lp, h, cfg)
            return h, None
        x, _ = _scan(tbody, x, p["trailing"], cfg)
    return x, _sum_aux(aux)


def _hybrid_flags(cfg: ModelConfig, n_groups: int):
    every = cfg.hybrid_attn_every
    return (jnp.arange(n_groups) % every) == (every - 1)


def _forward_enc_dec(p, inputs, cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)
    enc = shard(inputs["enc_embeds"].astype(dt), "batch", "seq", None)

    def enc_body(h, lp):
        h, aux, _ = blocks.decoder_block(lp, h, cfg, causal=False)
        return h, aux
    enc, enc_aux = _scan(enc_body, enc, p["enc_blocks"], cfg)
    enc = nn.rmsnorm_apply(p["enc_ln"], enc)

    x = p["dec_embed"].astype(dt)[inputs["tokens"]]
    x = shard(x, "batch", "seq", None)

    def dec_body(h, lp):
        kv = attn_mod.encode_kv(lp["xattn"], enc, cfg)
        h, aux, _ = blocks.decoder_block(lp, h, cfg, causal=True, cross_kv=kv)
        return h, aux
    x, dec_aux = _scan(dec_body, x, p["dec_blocks"], cfg)
    x = nn.rmsnorm_apply(p["dec_ln"], x)
    logits = x @ p["lm_head"].astype(x.dtype)
    logits = shard(logits, "batch", "seq", "vocab")
    aux = {k: _sum_aux(enc_aux).get(k, 0.0) + _sum_aux(dec_aux).get(k, 0.0)
           for k in ("load_balance", "router_z")}
    return logits, aux


# ===================================================================== loss
def loss_fn(p: Params, batch: dict[str, jnp.ndarray], cfg: ModelConfig):
    logits, aux = forward(p, batch, cfg)
    labels = batch["labels"]
    mask = batch.get("mask")
    lf = logits.astype(jnp.float32)
    if cfg.logits_microbatch > 1:
        # chunk the softmax over the sequence dim to bound live logits memory
        nchunks = cfg.logits_microbatch
        s = labels.shape[1]
        assert s % nchunks == 0
        cs = s // nchunks
        def chunk_loss(i):
            sl = jax.lax.dynamic_slice_in_dim(lf, i * cs, cs, axis=1)
            ll = jax.lax.dynamic_slice_in_dim(labels, i * cs, cs, axis=1)
            return _xent(sl, ll)
        per = jax.lax.map(chunk_loss, jnp.arange(nchunks))
        token_loss = jnp.moveaxis(per, 0, 1).reshape(labels.shape)
    else:
        token_loss = _xent(lf, labels)
    if mask is not None:
        denom = jnp.maximum(jnp.sum(mask), 1.0)
        loss = jnp.sum(token_loss * mask) / denom
    else:
        loss = jnp.mean(token_loss)
    total = loss + sum(v for k, v in aux.items() if k in LOSSES) \
        if aux else loss
    metrics = {"loss": loss, **{f"aux/{k}": v for k, v in aux.items()}}
    return total, metrics


def _xent(logits_f32, labels):
    lse = jax.nn.logsumexp(logits_f32, axis=-1)
    gold = jnp.take_along_axis(logits_f32, labels[..., None], axis=-1)[..., 0]
    return lse - gold


# ============================================================ prefill / decode
def prefill(p: Params, inputs: dict[str, jnp.ndarray], cfg: ModelConfig,
            max_len: int):
    """Forward over the prompt, building decode caches sized ``max_len``.
    Returns (last_token_logits, caches)."""
    if cfg.family == "enc_dec":
        return _prefill_enc_dec(p, inputs, cfg, max_len)
    x = embed_inputs(p, inputs, cfg)
    s = x.shape[1]

    if cfg.family in ("dense", "moe", "vlm"):
        parts = []
        for name, c, _, _ in layer_stacks(cfg):
            def body(h, lp, c=c):
                h, _, cache = blocks.decoder_block(lp, h, c, causal=True,
                                                   return_cache=True)
                return h, cache
            x, part = _scan(body, x, p[name], cfg)
            parts.append(part)
        caches = _pad_kv_caches(_concat_layers(parts), cfg, max_len)
    elif cfg.family == "ssm":
        def body(h, lp):
            h, st = blocks.mamba_block(lp, h, cfg, return_state=True)
            return h, st
        x, caches = _scan(body, x, p["blocks"], cfg)
    elif cfg.family == "hybrid":
        x, caches = _hybrid_prefill(p, x, cfg, max_len)
    else:
        raise ValueError(cfg.family)

    x = nn.rmsnorm_apply(p["ln_f"], x[:, -1:])
    logits = (x @ p["lm_head"].astype(x.dtype))[:, 0]
    return logits, caches


def _kv_cache_len(cfg: ModelConfig, max_len: int) -> int:
    """Rolling window cache for SWA models (production ring buffer)."""
    return min(max_len, cfg.window) if cfg.window else max_len


def _pad_kv_caches(caches, cfg: ModelConfig, max_len: int):
    m = _kv_cache_len(cfg, max_len)

    def pad(kv):   # (L, B, S, ...) -> (L, B, m, ...)
        l, b, s, *rest = kv.shape
        if s == m:
            return jnp.roll(kv, s % m, axis=2) if s % m else kv
        if s > m:   # keep the last window, rolled so slot(p) = p % m
            return jnp.roll(kv[:, :, s - m:], s % m, axis=2)
        buf = jnp.zeros((l, b, m, *rest), kv.dtype)
        return jax.lax.dynamic_update_slice(buf, kv, (0,) * kv.ndim)

    return {n: c if n == "len" else pad(c) for n, c in caches.items()}


def _hybrid_prefill(p, x, cfg: ModelConfig, max_len: int):
    n_groups = cfg.n_layers // cfg.hybrid_group
    flags = _hybrid_flags(cfg, n_groups)

    def body(h, xs):
        gp, flag = xs
        h, states, cache = blocks.hybrid_group(gp, p["shared_attn"], h, cfg,
                                               flag, return_state=True)
        return h, (states, cache)
    x, (states, attn_caches) = _scan(body, x, (p["groups"], flags), cfg)
    attn_caches = _pad_kv_caches(attn_caches, cfg, max_len)
    caches = {"mamba": states, "attn": attn_caches}
    if "trailing" in p:
        def tbody(h, lp):
            h, st = blocks.mamba_block(lp, h, cfg, return_state=True)
            return h, st
        x, tstates = _scan(tbody, x, p["trailing"], cfg)
        caches["trailing"] = tstates
    return x, caches


def _prefill_enc_dec(p, inputs, cfg: ModelConfig, max_len: int):
    dt = jnp.dtype(cfg.dtype)
    enc = inputs["enc_embeds"].astype(dt)

    def enc_body(h, lp):
        h, _, _ = blocks.decoder_block(lp, h, cfg, causal=False)
        return h, None
    enc, _ = _scan(enc_body, enc, p["enc_blocks"], cfg)
    enc = nn.rmsnorm_apply(p["enc_ln"], enc)

    x = p["dec_embed"].astype(dt)[inputs["tokens"]]

    def dec_body(h, lp):
        kv = attn_mod.encode_kv(lp["xattn"], enc, cfg)
        h, _, cache = blocks.decoder_block(lp, h, cfg, causal=True,
                                           return_cache=True, cross_kv=kv)
        return h, (cache, kv)
    x, (self_caches, cross_kvs) = _scan(dec_body, x, p["dec_blocks"], cfg)
    x = nn.rmsnorm_apply(p["dec_ln"], x[:, -1:])
    logits = (x @ p["lm_head"].astype(x.dtype))[:, 0]
    return logits, {"self": _pad_kv_caches(self_caches, cfg, max_len),
                    "cross": cross_kvs}


def decode_step(p: Params, caches, tokens: jnp.ndarray, cfg: ModelConfig, *,
                pt: jnp.ndarray | None = None,
                active: jnp.ndarray | None = None):
    """One decode step.  tokens: (B,) int32 -> (logits (B, vocab), caches).

    ``pt`` (B, n_pages) routes attention-family cache traffic through a paged
    store (see :func:`alloc_paged_caches`); ``active`` (B,) masks rows that
    must neither write real pages nor advance (idle slots, slots mid
    chunked-prefill) — their scatters land in the trash page."""
    dt = jnp.dtype(cfg.dtype)
    if cfg.family == "enc_dec":
        return _decode_enc_dec(p, caches, tokens, cfg)
    if cfg.family in ("dense", "moe", "vlm"):
        logits, caches, _ = decode_tokens(p, caches, tokens[:, None], cfg,
                                          pt=pt, active=active)
        return logits[:, 0], caches
    if pt is not None:
        raise ValueError(f"paged decode supports attention families "
                         f"(dense/moe/vlm), not {cfg.family!r}")
    x = p["embed"].astype(dt)[tokens][:, None, :]       # (B, 1, d)
    x = shard(x, "batch", "seq", None)

    if cfg.family == "ssm":
        def body(h, xs):
            lp, st = xs
            h, st = blocks.mamba_block(lp, h, cfg, state=st)
            return h, st
        x, caches = _scan(body, x, (p["blocks"], caches), cfg)
    elif cfg.family == "hybrid":
        x, caches = _hybrid_decode(p, x, caches, cfg)
    else:
        raise ValueError(cfg.family)

    x = nn.rmsnorm_apply(p["ln_f"], x)
    logits = (x @ p["lm_head"].astype(x.dtype))[:, 0]
    return logits, caches


def decode_tokens(p: Params, caches, tokens: jnp.ndarray, cfg: ModelConfig, *,
                  pt: jnp.ndarray | None = None,
                  active: jnp.ndarray | None = None,
                  n_valid: jnp.ndarray | None = None,
                  embeds: jnp.ndarray | None = None):
    """Cache-advancing forward over ``tokens`` (B, S) for the attention
    families -> (logits (B, S, vocab), caches, counts).

    The S == 1 case is the lockstep decode step; S > 1 is a *chunked
    prefill* step (a prompt chunk pushed through the decode path, so long
    prompts interleave with decode instead of stalling the batch).  The
    paged-cache routing keys are injected into each layer's cache dict and
    consumed (and stripped) by ``attention.py``'s paged branch:

    * ``pt`` (B, n_pages) int32 — per-slot page tables over the page store;
    * ``active`` (B,) bool — rows that may write real pages and advance;
    * ``n_valid`` scalar — how many of the S positions are real (a padded
      final chunk advances ``len`` by n_valid and its logits are read at
      position n_valid - 1).

    ``embeds`` (B, S, d) replaces the token embedding lookup for
    embedding-prompt (VLM) chunks.  ``counts`` sums the layers' work
    counters over a paged step (the MoE layers' ``moe_local_rows`` and
    ``moe_rows``; empty for other FFNs and for the slot caches).
    """
    if cfg.family not in ("dense", "moe", "vlm"):
        raise ValueError(f"decode_tokens supports attention families "
                         f"(dense/moe/vlm), not {cfg.family!r}")
    dt = jnp.dtype(cfg.dtype)
    x = embeds.astype(dt) if embeds is not None \
        else p["embed"].astype(dt)[tokens]              # (B, S, d)
    x = shard(x, "batch", "seq", None)

    if pt is not None:
        x, caches, counts = _paged_layers(p, x, caches, cfg, pt=pt,
                                          active=active, n_valid=n_valid)
    else:
        stacks = layer_stacks(cfg)
        parts, counts = [], {}
        for (name, c, _, _), part in zip(stacks,
                                         _split_layers(caches, stacks)):
            def body(h, xs, c=c):
                lp, cache = xs
                h, _, cache = blocks.decoder_block(
                    lp, h, c, causal=True, pos_offset=cache["len"],
                    cache=cache)
                return h, cache

            x, part = _scan(body, x, (p[name], part), cfg)
            parts.append(part)
        caches = _concat_layers(parts)
    x = nn.rmsnorm_apply(p["ln_f"], x)
    logits = x @ p["lm_head"].astype(x.dtype)
    return logits, caches, counts


def _valid(b: int, s: int, route: dict):
    """(B, S) real tokens of a paged step: active rows, and positions below
    ``n_valid``; None where every token is real."""
    v = None
    if "active" in route:
        v = jnp.broadcast_to(route["active"][:, None], (b, s))
    if "n_valid" in route:
        m = jnp.broadcast_to(jnp.arange(s)[None, :] < route["n_valid"],
                             (b, s))
        v = m if v is None else v & m
    return v


def _paged_layers(p, x, caches, cfg: ModelConfig, **route):
    """The layer loop over the paged store (L, P, ps, ...) -> (x, caches,
    counts).

    Each page store (``k``/``v``, or MLA's ``latent``) rides the
    loop's carry as one flat (L*P, ps, ...) store, layer ``l``'s page ``i``
    at ``l*P + i`` (its trash page at ``l*P``), and each layer's token
    writes and gather address that store in place through the ``base``
    routing key.  Scanned as xs/ys instead, every step would slice each
    layer's store out for the gather, restack the written slices into a new
    store and copy it back to the donated buffer.  Only the per-layer
    ``len`` (L, B) is scanned over; the stores ride through each stack of
    :func:`layer_stacks` in turn.
    """
    names = [n for n in caches if n != "len"]
    n_layers, n_pages = caches[names[0]].shape[:2]
    stores = {n: caches[n].reshape((n_layers * n_pages,) + caches[n].shape[2:])
              for n in names}
    route = {k: v for k, v in route.items() if v is not None}
    valid = _valid(*x.shape[:2], route) if cfg.family == "moe" else None
    stacks = layer_stacks(cfg)
    lens, counts = [], {}
    for (name, c, first, n), part in zip(stacks,
                                         _split_layers(caches["len"], stacks)):
        def body(carry, xs, c=c):
            h, stores = carry
            lp, ln, layer = xs
            cache = dict(stores, len=ln, base=layer * n_pages, **route)
            h, aux, cache = blocks.decoder_block(
                lp, h, c, causal=True, pos_offset=ln, cache=cache,
                valid=valid)
            return (h, {n: cache[n] for n in stores}), (cache["len"],
                                                       _counts(aux))

        layers = jnp.arange(first, first + n, dtype=jnp.int32)
        (x, stores), (ln, cnt) = _scan(body, (x, stores),
                                       (p[name], part, layers), cfg)
        lens.append(ln)
        counts = _add_aux(counts, _sum_aux(cnt))
    return x, dict(caches, len=_concat_layers(lens),
                   **{n: s.reshape(caches[n].shape)
                      for n, s in stores.items()}), counts


def _hybrid_decode(p, x, caches, cfg: ModelConfig):
    n_groups = cfg.n_layers // cfg.hybrid_group
    flags = _hybrid_flags(cfg, n_groups)

    def body(h, xs):
        (gp, flag), (states, cache) = xs
        h, states, cache = blocks.hybrid_group(
            gp, p["shared_attn"], h, cfg, flag, states=states,
            attn_cache=cache, pos_offset=cache["len"])
        return h, (states, cache)
    x, (mstates, acaches) = _scan(
        body, x, ((p["groups"], flags), (caches["mamba"], caches["attn"])), cfg)
    new = {"mamba": mstates, "attn": acaches}
    if "trailing" in p:
        def tbody(h, xs):
            lp, st = xs
            h, st = blocks.mamba_block(lp, h, cfg, state=st)
            return h, st
        x, tstates = _scan(tbody, x, (p["trailing"], caches["trailing"]), cfg)
        new["trailing"] = tstates
    return x, new


# ====================================================== cache logical axes
def cache_logical_axes(cfg: ModelConfig):
    """Logical partition axes for each decode-cache leaf (the same tree
    structure ``prefill`` returns, for every family).  This is the canonical
    table both training (``launch.steps.cache_shardings``) and sharded
    serving consume — under a 1-D ``("model",)`` serving mesh only the
    head-like axes (kv_heads / ssm_heads / conv_ch) resolve to a mesh axis,
    which is exactly the seam paged and per-slot stores shard on."""
    kv = {"k": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
          "v": ("layers", "batch", "kv_seq", "kv_heads", "head_dim"),
          "len": ("layers",)}
    if cfg.mla:
        kv = {"latent": ("layers", "batch", "kv_seq", None),
              "len": ("layers",)}
    ssm = {"conv": (None, "batch", None, "conv_ch"),
           "ssd": (None, "batch", "ssm_heads", "ssm_state", None)}
    ssm_g = {"conv": (None, None, "batch", None, "conv_ch"),
             "ssd": (None, None, "batch", "ssm_heads", "ssm_state", None)}
    if cfg.family in ("dense", "moe", "vlm"):
        return kv
    if cfg.family == "ssm":
        return ssm
    if cfg.family == "hybrid":
        out = {"mamba": ssm_g, "attn": kv}
        if cfg.n_layers % cfg.hybrid_group:
            out["trailing"] = ssm
        return out
    if cfg.family == "enc_dec":
        x = ("layers", "batch", "kv_seq", "kv_heads", "head_dim")
        return {"self": kv, "cross": (x, x)}
    raise ValueError(cfg.family)


def _is_logical_leaf(x) -> bool:
    return isinstance(x, tuple) and all(a is None or isinstance(a, str)
                                        for a in x)


def serve_cache_axes(cfg: ModelConfig, slot_axes):
    """Adapt :func:`cache_logical_axes` to the serving cache layouts.

    ``slot_axes`` is the per-leaf marker tree ``alloc_slot_caches`` /
    ``alloc_paged_caches`` return: leaves marked :data:`SLOT_AXIS_SHARED`
    gained a TRAILING slot axis (per-layer ``len`` scalars became
    ``(L, capacity)``), so their logical tuple gains a trailing ``None``;
    paged stores keep the canonical 5-axis tuple (the page-id dim sits where
    ``batch`` was and resolves to the same mesh axes — replicated on a
    model-only serving mesh).  The result feeds ``dist.partition``
    (``tree_shardings`` / ``resolve_spec``) directly.
    """
    logical = cache_logical_axes(cfg)
    return jax.tree.map(
        lambda la, ax: tuple(la) + (None,) if ax == SLOT_AXIS_SHARED
        else tuple(la),
        logical, slot_axes, is_leaf=_is_logical_leaf)


# ============================================== per-slot caches (cont. batching)
# The continuous-batching engine (repro.serve.engine.ContinuousEngine) keeps a
# fixed-capacity decode batch whose slots hold independent requests.  Each
# request is prefilled alone (batch 1) and its cache segment is spliced into
# its slot; cache-position leaves ("len") become per-slot vectors so decode
# masks/rope run at each slot's own offset (attention.py handles the (B,)
# form).  The batch axis of every cache leaf is discovered STRUCTURALLY — by
# diffing ``jax.eval_shape`` of prefill at two batch sizes — so the helpers
# work for every family (KV caches, SSM states, hybrid, enc-dec) without a
# per-family layout table.

#: sentinel axis for cache leaves whose shape does not depend on batch (the
#: per-layer "len" scalars); they gain a trailing slot axis instead
SLOT_AXIS_SHARED = -1


def _cache_shapes(p: Params, cfg: ModelConfig, max_len: int, batch: int,
                  example_inputs: dict[str, jnp.ndarray]):
    """Shape-only prefill -> decode-cache ShapeDtypeStructs at ``batch``."""
    inputs = {k: jax.ShapeDtypeStruct((batch,) + tuple(v.shape[1:]),
                                      jnp.asarray(v).dtype)
              for k, v in example_inputs.items()}
    _, caches = jax.eval_shape(
        functools.partial(prefill, cfg=cfg, max_len=max_len), p, inputs)
    return caches


def slot_cache_axes(p: Params, cfg: ModelConfig, max_len: int,
                    example_inputs: dict[str, jnp.ndarray]):
    """Per-leaf batch axis of the decode-cache pytree.

    Exactly one axis of each batch-dependent leaf changes when the prefill
    batch changes (batch enters every leaf at most once); leaves that do not
    change (cache-position scalars) map to :data:`SLOT_AXIS_SHARED`.
    """
    a = _cache_shapes(p, cfg, max_len, 2, example_inputs)
    b = _cache_shapes(p, cfg, max_len, 3, example_inputs)

    def axis(sa, sb) -> int:
        diff = [i for i, (x, y) in enumerate(zip(sa.shape, sb.shape))
                if x != y]
        if not diff:
            return SLOT_AXIS_SHARED
        assert len(diff) == 1, f"ambiguous batch axis for {sa.shape}"
        return diff[0]

    return jax.tree.map(axis, a, b)


def alloc_slot_caches(p: Params, cfg: ModelConfig, capacity: int,
                      max_len: int, example_inputs: dict[str, jnp.ndarray]):
    """Zero-initialized decode caches for ``capacity`` slots.

    Shared (batch-independent) leaves become per-slot vectors via a trailing
    slot axis — scanning over the layer axis then yields a (B,) ``len`` per
    layer, which the attention/rope per-slot paths consume directly.
    Returns ``(caches, axes)``; ``axes`` is what insert/evict need.
    """
    shapes = _cache_shapes(p, cfg, max_len, 1, example_inputs)
    axes = slot_cache_axes(p, cfg, max_len, example_inputs)

    def alloc(leaf, ax):
        if ax == SLOT_AXIS_SHARED:
            return jnp.zeros(leaf.shape + (capacity,), leaf.dtype)
        shp = list(leaf.shape)
        shp[ax] = capacity
        return jnp.zeros(shp, leaf.dtype)

    return jax.tree.map(alloc, shapes, axes), axes


def insert_slots(caches, group_caches, slots, axes):
    """Splice a batch-G prefill cache into slots ``slots`` ((G,) int32) of a
    batched cache — one scatter per leaf, so admitting a whole same-length
    group costs one dispatch instead of G cache-sized copies.

    ``slots`` may hold any (non-contiguous) slot ids; the group must share
    one prompt length, so shared leaves (per-layer lengths) are one scalar
    per layer broadcast across the group's slots.
    """
    g = slots.shape[0]

    def ins(batch_leaf, grp, ax):
        grp = jnp.asarray(grp).astype(batch_leaf.dtype)
        if ax == SLOT_AXIS_SHARED:
            tiled = jnp.broadcast_to(grp[..., None], grp.shape + (g,))
            return batch_leaf.at[..., slots].set(tiled)
        moved = jnp.moveaxis(batch_leaf, ax, 0)
        moved = moved.at[slots].set(jnp.moveaxis(grp, ax, 0))
        return jnp.moveaxis(moved, 0, ax)

    return jax.tree.map(ins, caches, group_caches, axes)


def insert_slot(caches, single_caches, slot, axes):
    """Batch-1 convenience wrapper over :func:`insert_slots`."""
    return insert_slots(caches, single_caches,
                        jnp.reshape(jnp.asarray(slot, jnp.int32), (1,)), axes)


def evict_slot(caches, slot, axes):
    """Invalidate slot ``slot``: zero its cache-position leaves so attention
    sees an empty prefix.  State leaves are left in place — the next
    ``insert_slot`` into this slot overwrites them wholesale, and per-slot
    masking/state flow keeps a stale slot from influencing any other."""
    def ev(leaf, ax):
        if ax != SLOT_AXIS_SHARED:
            return leaf
        zero = jnp.zeros(leaf.shape[:-1] + (1,), leaf.dtype)
        return jax.lax.dynamic_update_slice_in_dim(leaf, zero, slot,
                                                   axis=leaf.ndim - 1)

    return jax.tree.map(ev, caches, axes)


# ================================================== paged caches (serve/pages)
# Paged serving memory: instead of per-slot contiguous max_len segments, the
# KV leaves become flat page stores (L, P, page_size, H, D) indexed through
# per-slot page tables ((B, n_pages) int32 rows the engine owns host-side and
# passes into every decode/chunk step).  Which leaves page is discovered
# STRUCTURALLY, like the batch axes above: a leaf pages iff its shape depends
# on max_len (KV caches do; SSM conv/ssm states and cross-attention context
# do not — those families keep dense per-slot segments and the engine gates
# paging to attention families).

#: sentinel axis for leaves stored as (L, num_pages, page_size, ...) pages
PAGED_AXIS = -2


def paged_cache_axes(p: Params, cfg: ModelConfig, max_len: int,
                     page_size: int,
                     example_inputs: dict[str, jnp.ndarray]):
    """Per-leaf paging/batch markers for the decode-cache pytree:
    :data:`PAGED_AXIS` for max_len-dependent (pageable) leaves, otherwise the
    leaf's batch axis exactly as :func:`slot_cache_axes` reports it."""
    baxes = slot_cache_axes(p, cfg, max_len, example_inputs)
    a = _cache_shapes(p, cfg, max_len, 1, example_inputs)
    b = _cache_shapes(p, cfg, max_len + page_size, 1, example_inputs)

    def mark(sa, sb, bax):
        diff = [i for i, (x, y) in enumerate(zip(sa.shape, sb.shape))
                if x != y]
        if not diff:
            return bax
        assert len(diff) == 1, f"ambiguous seq axis for {sa.shape}"
        # the paged layout assumes a stacked cache leaf (layers, batch,
        # seq, heads, head_dim), or (layers, batch, seq, dim) for a latent
        assert (bax, diff[0]) == (1, 2) and len(sa.shape) in (4, 5), \
            f"unpageable cache leaf {sa.shape} (batch={bax}, seq={diff[0]})"
        return PAGED_AXIS

    return jax.tree.map(mark, a, b, baxes)


def alloc_paged_caches(p: Params, cfg: ModelConfig, capacity: int,
                       max_len: int, page_size: int, num_pages: int,
                       example_inputs: dict[str, jnp.ndarray]):
    """Zero-initialized paged decode caches.

    Pageable leaves become (L, num_pages, page_size, ...) stores shared by
    every slot (page ids are layer-invariant: a slot's page holds that page's
    positions in every layer).  Non-pageable leaves allocate exactly like
    :func:`alloc_slot_caches` — shared leaves (per-layer ``len``) gain a
    trailing slot axis.  Returns ``(caches, axes)``.
    """
    if cfg.window is not None:
        raise ValueError("paged caches are incompatible with sliding-window "
                         "ring buffers (cfg.window)")
    axes = paged_cache_axes(p, cfg, max_len, page_size, example_inputs)
    # evaluated at max_len == page_size, a pageable leaf's shape IS one
    # page's shape with the batch axis in page-id position
    shapes = _cache_shapes(p, cfg, page_size, 1, example_inputs)

    def alloc(leaf, ax):
        if ax == PAGED_AXIS:
            shp = list(leaf.shape)
            shp[1] = num_pages
            return jnp.zeros(shp, leaf.dtype)
        if ax == SLOT_AXIS_SHARED:
            return jnp.zeros(leaf.shape + (capacity,), leaf.dtype)
        shp = list(leaf.shape)
        shp[ax] = capacity
        return jnp.zeros(shp, leaf.dtype)

    return jax.tree.map(alloc, shapes, axes), axes


def insert_pages(caches, group_caches, slots, pages, axes):
    """Splice a batch-G prefill cache (built at max_len rounded up to a page
    multiple, so its seq extent is ``n_pg * page_size``) into the page store:
    one scatter per pageable leaf at the groups' page ids ``pages``
    ((G, n_pg) int32), plus the usual per-slot scatter for everything else.
    """
    g = slots.shape[0]
    flat_pages = jnp.reshape(pages, (-1,))

    def ins(leaf, grp, ax):
        grp = jnp.asarray(grp).astype(leaf.dtype)
        if ax == PAGED_AXIS:
            l, _, r, *rest = grp.shape            # (L, G, n_pg*ps, ...)
            ps = leaf.shape[2]
            content = grp.reshape(l, g * (r // ps), ps, *rest)
            return leaf.at[:, flat_pages].set(content)
        if ax == SLOT_AXIS_SHARED:
            tiled = jnp.broadcast_to(grp[..., None], grp.shape + (g,))
            return leaf.at[..., slots].set(tiled)
        moved = jnp.moveaxis(leaf, ax, 0)
        moved = moved.at[slots].set(jnp.moveaxis(grp, ax, 0))
        return jnp.moveaxis(moved, 0, ax)

    return jax.tree.map(ins, caches, group_caches, axes)


def set_slot_lens(caches, slot, value, axes):
    """Set slot ``slot``'s cache-position leaves to ``value`` (prefix-cache
    hits start a slot at the shared-prefix length without any KV traffic)."""
    def st(leaf, ax):
        if ax != SLOT_AXIS_SHARED:
            return leaf
        return leaf.at[..., slot].set(jnp.asarray(value, leaf.dtype))

    return jax.tree.map(st, caches, axes)


def slot_view(caches, slot, axes):
    """A batch-1 view of one slot: per-slot leaves sliced at ``slot`` (a
    traced scalar is fine), page stores passed through whole — chunked
    prefill runs a single slot without dragging the full batch through the
    compute."""
    def ex(leaf, ax):
        if ax == PAGED_AXIS:
            return leaf
        if ax == SLOT_AXIS_SHARED:
            return jax.lax.dynamic_slice_in_dim(leaf, slot, 1,
                                                axis=leaf.ndim - 1)
        return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=ax)

    return jax.tree.map(ex, caches, axes)


def merge_slot(caches, view, slot, axes):
    """Write a :func:`slot_view` back: page stores replace wholesale (their
    writes already landed at absolute page ids), per-slot leaves scatter at
    ``slot``."""
    def mg(leaf, sub, ax):
        if ax == PAGED_AXIS:
            return sub
        if ax == SLOT_AXIS_SHARED:
            return jax.lax.dynamic_update_slice_in_dim(
                leaf, sub.astype(leaf.dtype), slot, axis=leaf.ndim - 1)
        return jax.lax.dynamic_update_slice_in_dim(
            leaf, sub.astype(leaf.dtype), slot, axis=ax)

    return jax.tree.map(mg, caches, view, axes)


def prefill_chunk(p: Params, caches, tokens: jnp.ndarray,
                  pt_row: jnp.ndarray, slot, n_valid, cfg: ModelConfig,
                  axes, embeds: jnp.ndarray | None = None):
    """One chunked-prefill step for one slot over the paged cache.

    ``tokens`` (1, chunk) is the next prompt chunk (zero-padded past
    ``n_valid`` on the final chunk — the fixed chunk shape is what bounds
    prefill recompilation to the number of chunk sizes, not prompt lengths);
    ``pt_row`` (1, n_pages) is the slot's page table.  Returns the logits at
    the last valid position ((1, vocab) — only meaningful on the final
    chunk), the updated caches and the step's counts (:func:`decode_tokens`).
    """
    view = slot_view(caches, slot, axes)
    logits, view, counts = decode_tokens(p, view, tokens, cfg, pt=pt_row,
                                         n_valid=n_valid, embeds=embeds)
    last = jax.lax.dynamic_slice_in_dim(logits, n_valid - 1, 1, axis=1)[:, 0]
    return last, merge_slot(caches, view, slot, axes), counts


def _decode_enc_dec(p, caches, tokens, cfg: ModelConfig):
    dt = jnp.dtype(cfg.dtype)
    x = p["dec_embed"].astype(dt)[tokens][:, None, :]

    def body(h, xs):
        lp, cache, kv = xs
        h, _, cache = blocks.decoder_block(
            lp, h, cfg, causal=True, pos_offset=cache["len"], cache=cache,
            cross_kv=kv)
        return h, cache
    x, self_caches = _scan(
        body, x, (p["dec_blocks"], caches["self"], caches["cross"]), cfg)
    x = nn.rmsnorm_apply(p["dec_ln"], x)
    logits = (x @ p["lm_head"].astype(x.dtype))[:, 0]
    return logits, {"self": self_caches, "cross": caches["cross"]}
