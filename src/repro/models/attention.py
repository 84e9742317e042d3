"""GQA attention with RoPE, qk-norm, sliding window, cross-attn and KV cache.

Training / prefill use the differentiable jnp path (or the SIP-tuned Pallas
kernel when ``cfg.use_pallas`` and the path is forward-only); decode operates
on a preallocated right-padded KV cache with one-token updates.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.dist.partition import shard
from repro.dist.tp import tp_allreduce
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.paged_attention import ops as pg_ops
from repro.models import modules as nn
from repro.models.config import ModelConfig

NEG_INF = -1e30


# ------------------------------------------------------------------ params
def phys_heads(cfg: ModelConfig) -> int:
    return max(cfg.padded_heads, cfg.n_heads) if cfg.padded_heads else cfg.n_heads


def kv_head_map(cfg: ModelConfig) -> jnp.ndarray | None:
    """Physical q-head -> kv-head index, preserving the ORIGINAL GQA grouping
    for the real heads; padded heads map to kv 0 (their wo rows are zero, so
    they contribute nothing).  None when no padding (reshape GQA is used)."""
    ph = phys_heads(cfg)
    if ph == cfg.n_heads:
        return None
    group = cfg.n_heads // cfg.n_kv_heads
    idx = [i // group for i in range(cfg.n_heads)] + [0] * (ph - cfg.n_heads)
    return jnp.asarray(idx, jnp.int32)


def _wo_eff(p, cfg: ModelConfig, dt) -> jnp.ndarray:
    """wo with padded-head rows hard-masked at USE.  The mask (not just the
    zero init) makes padded-head gradients exactly zero for both wq (via the
    zero output path) and wo (via the multiplicative mask), so padding stays
    inert under training — tests/test_perf_levers.py."""
    wo = p["wo"].astype(dt)
    ph = wo.shape[0]
    if ph != cfg.n_heads:
        mask = (jnp.arange(ph) < cfg.n_heads).astype(dt)
        wo = wo * mask[:, None, None]
    return wo


def init_attention(key, cfg: ModelConfig, cross: bool = False):
    hd, h, hkv, d = cfg.hd, phys_heads(cfg), cfg.n_kv_heads, cfg.d_model
    ks = jax.random.split(key, 6)
    wq = jax.random.normal(ks[0], (d, h, hd)) * d ** -0.5
    wo = jax.random.normal(ks[3], (h, hd, d)) * (cfg.n_heads * hd) ** -0.5
    if h != cfg.n_heads:                       # zero the padded head slices
        wq = wq.at[:, cfg.n_heads:, :].set(0.0)
        wo = wo.at[cfg.n_heads:, :, :].set(0.0)
    p = {
        "wq": nn.Param(wq, ("embed", "heads", "head_dim")),
        "wk": nn.param(ks[1], (d, hkv, hd), ("embed", "kv_heads", "head_dim"),
                       scale=d ** -0.5),
        "wv": nn.param(ks[2], (d, hkv, hd), ("embed", "kv_heads", "head_dim"),
                       scale=d ** -0.5),
        "wo": nn.Param(wo, ("heads", "head_dim", "embed")),
    }
    if cfg.qk_norm:
        p["q_norm"] = nn.param(ks[4], (hd,), ("head_dim",), init="ones")
        p["k_norm"] = nn.param(ks[5], (hd,), ("head_dim",), init="ones")
    del cross
    return p


# ------------------------------------------------------------------- rope
def rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, H, D) with D even; pos: (S,) absolute positions shared by the
    batch, or (B, S) per-sequence positions (continuous-batching decode, where
    every slot sits at its own offset)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None] * freqs   # (S, half) | (B, S, half)
    if pos.ndim == 2:
        cos = jnp.cos(ang)[:, :, None, :]
        sin = jnp.sin(ang)[:, :, None, :]
    else:
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def _qkv(p, x: jnp.ndarray, cfg: ModelConfig, pos: jnp.ndarray,
         rotary: bool = True):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if cfg.qk_norm:
        q = nn.rmsnorm_apply(p["q_norm"], q)
        k = nn.rmsnorm_apply(p["k_norm"], k)
    if rotary:
        q = rope(q, pos, cfg.rope_theta)
        k = rope(k, pos, cfg.rope_theta)
    return q, k, v


def _sdpa(q, k, v, *, causal: bool, window: int | None,
          kv_len: jnp.ndarray | None = None,
          kv_idx: jnp.ndarray | None = None) -> jnp.ndarray:
    """q: (B,Sq,H,D); k,v: (B,Skv,Hkv,D) -> (B,Sq,H,D).  jnp path (GQA).

    ``kv_len``: optional scalar — only cache positions < kv_len are valid
    (decode with a preallocated cache) — or a (B,) vector for per-slot decode
    (continuous batching: every slot has its own valid prefix).  ``kv_idx``:
    explicit q-head -> kv head map (padded-heads mode); kv is gathered to full
    head count so the heads dim shards over 'model'."""
    if kv_idx is not None:
        k = shard(k[:, :, kv_idx, :], "batch", "seq", "heads", "head_dim")
        v = shard(v[:, :, kv_idx, :], "batch", "seq", "heads", "head_dim")
    b, sq, h, dh = q.shape
    _, skv, hkv, _ = k.shape
    group = h // hkv
    qg = q.reshape(b, sq, hkv, group, dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                   k.astype(jnp.float32)) * (dh ** -0.5)
    if kv_len is not None and jnp.ndim(kv_len) > 0:
        # per-slot: mask is (B, sq, skv).  A fully-masked row (an empty slot,
        # kv_len == 0) stays finite — NEG_INF is a large negative, not -inf,
        # so softmax degrades to uniform and the garbage output is confined
        # to that slot (the engine discards it).
        rows = jnp.arange(sq)[None, :, None] + (kv_len[:, None, None] - sq)
        cols = jnp.arange(skv)[None, None, :]
        mask = jnp.broadcast_to(cols < kv_len[:, None, None], (b, sq, skv))
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= cols > rows - window
        s = jnp.where(mask[:, None, None], s, NEG_INF)
    else:
        rows = jnp.arange(sq)[:, None] + (skv - sq)
        if kv_len is not None:
            rows = jnp.arange(sq)[:, None] + (kv_len - sq)
        cols = jnp.arange(skv)[None, :]
        mask = jnp.ones((sq, skv), bool)
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= cols > rows - window
        if kv_len is not None:
            mask &= cols < kv_len
        s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, h, dh).astype(q.dtype)


def attention(p, x: jnp.ndarray, cfg: ModelConfig, *,
              causal: bool = True,
              pos_offset: int | jnp.ndarray = 0,
              cache: dict[str, Any] | None = None,
              return_cache: bool = False):
    """Self-attention.  Modes:
      train/prefill: cache=None (optionally return_cache -> fresh cache)
      decode: cache={'k','v','len'} preallocated; x is (B, 1, d)

    ``pos_offset`` / ``cache['len']`` may be (B,) vectors — per-slot decode
    for the continuous-batching engine, where each batch slot holds a request
    at its own sequence position.
    """
    b, s, d = x.shape
    if jnp.ndim(pos_offset) > 0:
        pos = jnp.arange(s)[None, :] + pos_offset[:, None]   # (B, S) per slot
    else:
        pos = jnp.arange(s) + pos_offset
    q, k, v = _qkv(p, x, cfg, pos)
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")
    kv_idx = kv_head_map(cfg)

    new_cache = None
    if cache is not None and "pt" in cache:     # paged decode / chunk prefill
        o, new_cache = _paged_decode(cache, q, k, v, cfg, causal=causal,
                                     kv_idx=kv_idx)
    elif cache is not None:                     # decode: append to cache
        idx = cache["len"]
        size = cache["k"].shape[1]
        # SWA ring buffer: slot(p) = p % size once the cache is window-sized
        rolling = cfg.window is not None and size <= cfg.window
        w_idx = idx % size if rolling else idx
        if jnp.ndim(idx) > 0:
            # per-slot decode (s == 1): scatter each slot's token at its own
            # cache position
            rows_b = jnp.arange(b)
            ck = cache["k"].at[rows_b, w_idx].set(
                k[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[rows_b, w_idx].set(
                v[:, 0].astype(cache["v"].dtype))
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], k.astype(cache["k"].dtype), (0, w_idx, 0, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], v.astype(cache["v"].dtype), (0, w_idx, 0, 0))
        new_cache = {"k": ck, "v": cv, "len": idx + s}
        # rolling: slot indices are not positions — causal/window masks do not
        # apply; every slot < min(len, size) is in-window by construction.
        o = _sdpa(q, ck, cv,
                  causal=causal and not rolling,
                  window=None if rolling else cfg.window,
                  kv_len=idx + s, kv_idx=kv_idx)
    else:
        if cfg.use_pallas and kv_idx is None:
            o = _pallas_sdpa(q, k, v, causal=causal, window=cfg.window)
        else:
            o = _sdpa(q, k, v, causal=causal, window=cfg.window,
                      kv_idx=kv_idx)
        if return_cache:
            new_cache = {"k": k, "v": v, "len": jnp.int32(s)}
    o = shard(o, "batch", "seq", "heads", "head_dim")
    # manual-TP seam: heads are the sharded contraction dim, so the wo
    # product is a partial sum per shard — reduced here (identity outside a
    # tp_context, so single-device and GSPMD paths are untouched)
    out = tp_allreduce(jnp.einsum("bshk,hkd->bsd", o, _wo_eff(p, cfg, x.dtype)))
    out = shard(out, "batch", "seq", "embed_act")
    if return_cache or cache is not None:
        return out, new_cache
    return out


def _paged_decode(cache: dict[str, Any], q, k, v, cfg: ModelConfig, *,
                  causal: bool, kv_idx):
    """Page-table-indirect cache write + read (continuous batching over a
    paged KV store).

    ``cache`` holds the flat page stores ``k``/``v`` (L*P, ps, Hkv, D) of
    every layer, this layer's per-slot lengths ``len`` (B,), and routing
    keys the model layer injects per step: ``pt`` (B, n_pages) int32 page
    tables, ``base`` scalar (this layer's page ``i`` is store page ``base +
    i``; its trash page is ``base``), optional ``active``
    (B,) bool (rows mid-chunked-prefill or idle write to the trash page and
    do not advance), optional ``n_valid`` scalar (chunked prefill: how many
    of the s positions are real tokens — padding still writes, but beyond
    ``len + n_valid`` positions are never read because the engine reserves
    worst-case pages per slot and reads are bounded by ``len``).

    Writes scatter each token at (base + pt[b, pos // ps], pos % ps);
    reads gather the slot's pages back into a contiguous (B, n*ps, Hkv, D)
    view (the SIP-tuned ``paged_gather`` kernel under ``cfg.use_pallas``)
    and reuse the per-slot masked SDPA unchanged.  Sliding-window archs
    keep the dense ring buffer (the engine gates paging to window=None
    families).
    """
    store_k, store_v, idx = cache["k"], cache["v"], cache["len"]
    pt = cache["pt"]
    base = cache["base"]
    active = cache.get("active")
    n_valid = cache.get("n_valid")
    b, s = q.shape[0], q.shape[1]
    ps = store_k.shape[1]
    n_pages = pt.shape[1]

    pos = idx[:, None] + jnp.arange(s)[None, :]            # (B, S) absolute
    # positions past the table's end go to the trash page: chunked-prefill
    # padding can overrun a full table (pos // ps == n_pages) and clamping
    # would scatter duplicate offsets onto the LAST real page, overwriting
    # live KV — the clamp below only keeps the gather index legal
    page_slot = pos // ps
    page_ids = jnp.take_along_axis(
        pt, jnp.minimum(page_slot, n_pages - 1), axis=1)   # (B, S)
    page_ids = jnp.where(page_slot < n_pages, page_ids, 0)
    if active is not None:
        page_ids = jnp.where(active[:, None], page_ids, 0)  # trash page
    page_ids = page_ids + base
    offs = pos % ps
    ck = store_k.at[page_ids, offs].set(k.astype(store_k.dtype))
    cv = store_v.at[page_ids, offs].set(v.astype(store_v.dtype))

    pt = pt + base
    gk = _gather_pages(ck, pt, cfg)                        # (B, n*ps, Hkv, D)
    gv = _gather_pages(cv, pt, cfg)
    o = _sdpa(q, gk, gv, causal=causal, window=None,
              kv_len=idx + s, kv_idx=kv_idx)

    adv = s if n_valid is None else n_valid
    if active is not None:
        adv = jnp.where(active, adv, 0)
    return o, {"k": ck, "v": cv, "len": idx + adv}


def _gather_pages(store, pt, cfg: ModelConfig):
    """(P, ps, H, D) store + (B, n) page table -> contiguous (B, n*ps, H, D)
    per-slot KV view; the SIP-registered kernel when ``cfg.use_pallas``."""
    if cfg.use_pallas:
        pages = pg_ops.paged_gather(store, pt)
    else:
        pages = jnp.take(store, pt, axis=0)
    b, n, ps, h, d = pages.shape
    return pages.reshape(b, n * ps, h, d)


def cross_attention(p, x: jnp.ndarray, ctx_kv: tuple[jnp.ndarray, jnp.ndarray],
                    cfg: ModelConfig) -> jnp.ndarray:
    """Decoder cross-attn over precomputed encoder K/V (no rotary, no mask)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    if cfg.qk_norm:
        q = nn.rmsnorm_apply(p["q_norm"], q)
    k, v = ctx_kv
    o = _sdpa(q, k, v, causal=False, window=None, kv_idx=kv_head_map(cfg))
    return tp_allreduce(jnp.einsum("bshk,hkd->bsd", o, _wo_eff(p, cfg, dt)))


def encode_kv(p, ctx: jnp.ndarray, cfg: ModelConfig):
    """Project encoder output once into cross-attention K/V."""
    dt = ctx.dtype
    k = jnp.einsum("bsd,dhk->bshk", ctx, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", ctx, p["wv"].astype(dt))
    if cfg.qk_norm:
        k = nn.rmsnorm_apply(p["k_norm"], k)
    return k, v


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  dtype=jnp.bfloat16) -> dict[str, Any]:
    shape = (batch, max_len, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
            "len": jnp.int32(0)}


def _pallas_sdpa(q, k, v, *, causal, window):
    """SIP-tuned Pallas kernel path (forward-only).  Layout: kernels expect
    (B, H, S, D).  The kernel is the ONE registry-cached instance for this
    variant (bound to the active schedule_cache), so repeated calls reuse
    its schedule/build caches instead of recompiling from scratch."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    kern = fa_ops.kernel(causal=causal, window=window)
    o = kern(qt, kt, vt)
    return jnp.swapaxes(o, 1, 2)
