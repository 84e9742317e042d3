"""GQA attention with RoPE, qk-norm, sliding window, cross-attn and KV cache,
and multi-head latent attention (MLA, DeepSeek-V2) over a latent cache.

Training / prefill use the differentiable jnp path (or the SIP-tuned Pallas
kernel when ``cfg.use_pallas`` and the path is forward-only); decode operates
on a preallocated right-padded KV cache with one-token updates.
"""

from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.dist.partition import shard
from repro.dist.tp import tp_allreduce
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.paged_attention import ops as pg_ops
from repro.models import modules as nn
from repro.models.config import ModelConfig, Yarn

NEG_INF = -1e30
#: lanes of a TPU vreg: a cached leaf whose last dim is not a multiple of
#: this gets a transposed default layout on the chip, which the page-store
#: scatter and the gather kernel can only reach through whole-store copies
LANES = 128


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
    if cfg.mla:
        return init_mla(key, cfg)
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
def rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float,
         yarn: Yarn | None = None) -> jnp.ndarray:
    """x: (B, S, H, D) with D even; pos: (S,) absolute positions shared by the
    batch, or (B, S) per-sequence positions (continuous-batching decode, where
    every slot sits at its own offset).  ``yarn`` blends in YaRN's
    interpolated frequencies and scales cos/sin by its mscale ratio."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if yarn is not None:
        freqs = yarn_freqs(freqs, yarn, theta)
    ang = pos.astype(jnp.float32)[..., None] * freqs   # (S, half) | (B, S, half)
    if pos.ndim == 2:
        cos = jnp.cos(ang)[:, :, None, :]
        sin = jnp.sin(ang)[:, :, None, :]
    else:
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]
    if yarn is not None:
        mult = yarn.m(yarn.mscale) / yarn.m(yarn.mscale_all_dim)
        if mult != 1.0:
            cos, sin = cos * mult, sin * mult
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                           axis=-1).astype(x.dtype)


def yarn_freqs(freqs: jnp.ndarray, yarn: Yarn, theta: float) -> jnp.ndarray:
    """YaRN's inverse frequencies: each rotary pair keeps its frequency
    (extrapolation) above the ``beta_fast`` correction dim, takes it over
    ``factor`` (interpolation) below ``beta_slow``'s, and ramps linearly
    between (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``)."""
    half = freqs.shape[0]
    d = 2 * half

    def dim(rotations):         # yarn_find_correction_dim
        return d * math.log(yarn.original_max / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(dim(yarn.beta_fast)), 0)
    hi = min(math.ceil(dim(yarn.beta_slow)), d - 1)
    if lo == hi:
        hi += 0.001
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - lo) / (hi - lo),
                    0.0, 1.0)
    return freqs / yarn.factor * ramp + freqs * (1.0 - ramp)


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
          kv_idx: jnp.ndarray | None = None,
          scale: float | None = None) -> jnp.ndarray:
    """q: (B,Sq,H,D); k: (B,Skv,Hkv,D), v: (B,Skv,Hkv,Dv) -> (B,Sq,H,Dv).
    jnp path (GQA); scores scaled by ``scale`` (default D ** -0.5).

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
                   k.astype(jnp.float32)) * \
        (dh ** -0.5 if scale is None else scale)
    mask = _mask(b, sq, skv, causal=causal, window=window, kv_len=kv_len)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32))
    return o.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)


def _mask(b: int, sq: int, skv: int, *, causal: bool, window: int | None,
          kv_len: jnp.ndarray | None):
    """Which keys each query sees: (B, Sq, Skv) where ``kv_len`` is a (B,)
    vector, else (1, Sq, Skv).  Queries are the last ``sq`` of the valid
    positions."""
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
        return mask
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
    return mask[None]


def _positions(s: int, pos_offset) -> jnp.ndarray:
    if jnp.ndim(pos_offset) > 0:
        return jnp.arange(s)[None, :] + pos_offset[:, None]   # (B, S) per slot
    return jnp.arange(s) + pos_offset


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
    at its own sequence position.  MLA configs go to :func:`mla`.
    """
    if cfg.mla:
        return mla(p, x, cfg, causal=causal, pos_offset=pos_offset,
                   cache=cache, return_cache=return_cache)
    b, s, d = x.shape
    pos = _positions(s, pos_offset)
    q, k, v = _qkv(p, x, cfg, pos)
    q = shard(q, "batch", "seq", "heads", "head_dim")
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")
    kv_idx = kv_head_map(cfg)

    new_cache = None
    if cache is not None and "pt" in cache:     # paged decode / chunk prefill
        views, new_cache = _paged_update(cache, {"k": k, "v": v}, cfg)
        o = _sdpa(q, views["k"], views["v"], causal=causal, window=None,
                  kv_len=cache["len"] + s, kv_idx=kv_idx)
    elif cache is not None:                     # decode: append to cache
        idx = cache["len"]
        size = cache["k"].shape[1]
        # SWA ring buffer: slot(p) = p % size once the cache is window-sized
        rolling = cfg.window is not None and size <= cfg.window
        w_idx = idx % size if rolling else idx
        new_cache = dict(_append(cache, {"k": k, "v": v}, w_idx),
                         len=idx + s)
        # rolling: slot indices are not positions — causal/window masks do not
        # apply; every slot < min(len, size) is in-window by construction.
        o = _sdpa(q, new_cache["k"], new_cache["v"],
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


def _append(cache: dict[str, Any], new: dict[str, jnp.ndarray], w_idx):
    """Write each of ``new``'s (B, S, ...) leaves into its contiguous
    per-slot cache at ``w_idx``: one scalar offset, or a (B,) vector (per-slot
    decode, S == 1: each slot's token at its own cache position)."""
    out = {}
    for n, x in new.items():
        store = cache[n]
        if jnp.ndim(w_idx) > 0:
            out[n] = store.at[jnp.arange(x.shape[0]), w_idx].set(
                x[:, 0].astype(store.dtype))
        else:
            out[n] = jax.lax.dynamic_update_slice(
                store, x.astype(store.dtype),
                (0, w_idx) + (0,) * (x.ndim - 2))
    return out


def _paged_update(cache: dict[str, Any], new: dict[str, jnp.ndarray],
                  cfg: ModelConfig):
    """Page-table-indirect cache write + read (continuous batching over a
    paged store).  Returns each store's gathered per-slot view and the
    updated cache.

    ``cache`` holds the flat page stores named in ``new`` (``k``/``v`` of
    (L*P, ps, H, D), or MLA's ``latent`` of (L*P, ps, D)), each over
    every layer, this
    layer's per-slot lengths ``len`` (B,), and routing keys the model layer
    injects per step: ``pt`` (B, n_pages) int32 page tables, ``base`` scalar
    (this layer's page ``i`` is store page ``base + i``; its trash page is
    ``base``), optional ``active``
    (B,) bool (rows mid-chunked-prefill or idle write to the trash page and
    do not advance), optional ``n_valid`` scalar (chunked prefill: how many
    of the s positions are real tokens — padding still writes, but beyond
    ``len + n_valid`` positions are never read because the engine reserves
    worst-case pages per slot and reads are bounded by ``len``).

    Writes scatter each token at (base + pt[b, pos // ps], pos % ps);
    reads gather the slot's pages back into a contiguous (B, n*ps, ...)
    view (the SIP-tuned ``paged_gather`` kernel under ``cfg.use_pallas``)
    for the per-slot masked attention, which reads keys below ``len + s``.
    Sliding-window archs keep the dense ring buffer (the engine gates
    paging to window=None families).
    """
    idx = cache["len"]
    pt = cache["pt"]
    base = cache["base"]
    active = cache.get("active")
    n_valid = cache.get("n_valid")
    b, s = next(iter(new.values())).shape[:2]
    ps = cache[next(iter(new))].shape[1]
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
    stores = {n: cache[n].at[page_ids, offs].set(x.astype(cache[n].dtype))
              for n, x in new.items()}

    pt = pt + base
    views = {n: _gather_pages(st, pt, cfg) for n, st in stores.items()}

    adv = s if n_valid is None else n_valid
    if active is not None:
        adv = jnp.where(active, adv, 0)
    return views, dict(stores, len=idx + adv)


def _gather_pages(store, pt, cfg: ModelConfig):
    """(P, ps, ...) store + (B, n) page table -> contiguous (B, n*ps, ...)
    per-slot view; the SIP-registered kernel when ``cfg.use_pallas``."""
    if cfg.use_pallas:
        pages = pg_ops.paged_gather(store, pt)
    else:
        pages = jnp.take(store, pt, axis=0)
    b, n, ps, *rest = pages.shape
    return pages.reshape(b, n * ps, *rest)


# --------------------------------------------------------------------- MLA
def init_mla(key, cfg: ModelConfig):
    """MLA without a query latent (DeepSeek-V2-Lite): ``wkv_a`` and
    ``wk_rope`` are ``kv_a_proj_with_mqa`` split at ``kv_lora_rank``,
    ``wk_b`` and ``wv_b`` are ``kv_b_proj`` split per head at
    ``qk_nope_dim``."""
    d, h, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, rp, vd = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    ks = jax.random.split(key, 7)
    return {
        "wq": nn.param(ks[0], (d, h, nope + rp), ("embed", "heads", None),
                       scale=d ** -0.5),
        "wkv_a": nn.param(ks[1], (d, r), ("embed", None), scale=d ** -0.5),
        "wk_rope": nn.param(ks[2], (d, rp), ("embed", None), scale=d ** -0.5),
        "kv_norm": nn.param(ks[3], (r,), (None,), init="ones"),
        "wk_b": nn.param(ks[4], (r, h, nope), (None, "heads", None),
                         scale=r ** -0.5),
        "wv_b": nn.param(ks[5], (r, h, vd), (None, "heads", None),
                         scale=r ** -0.5),
        "wo": nn.param(ks[6], (h, vd, d),
                       ("heads", None, "embed"), scale=(h * vd) ** -0.5),
    }


def mla_rope(x: jnp.ndarray, pos: jnp.ndarray, cfg: ModelConfig):
    """DeepSeek-V2's rotary embedding: the rope dims are de-interleaved
    (pairs (x0, x1), (x2, x3), ... become x0 x2 ... | x1 x3 ...) and then
    rotated as halves, with YaRN's frequencies."""
    d = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (d // 2, 2)).swapaxes(-1, -2).reshape(x.shape)
    return rope(x, pos, cfg.rope_theta, cfg.rope_yarn)


def mla(p, x: jnp.ndarray, cfg: ModelConfig, *, causal: bool = True,
        pos_offset: int | jnp.ndarray = 0,
        cache: dict[str, Any] | None = None, return_cache: bool = False):
    """Multi-head latent attention.  The cache holds one ``latent`` vector
    per token: the RMS-normed latent ``c_kv`` (kv_lora_rank,) then the
    roped key ``k_pe`` (qk_rope_dim,), zero-filled to a whole number of
    :data:`LANES`, with no head axis: a unit axis there would pad a page
    store's device layout twofold.  Cached reads (paged or per-slot decode,
    and chunks) attend in the absorbed form (:func:`_mla_absorbed`);
    training and whole-prompt prefill expand keys and values per head."""
    b, s, _ = x.shape
    pos = _positions(s, pos_offset)
    dt = x.dtype
    nope = cfg.qk_nope_dim
    with jax.named_scope("mla"):
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
        q_nope, q_pe = q[..., :nope], mla_rope(q[..., nope:], pos, cfg)
        c_kv = nn.rmsnorm_apply(p["kv_norm"], x @ p["wkv_a"].astype(dt))
        k_pe = mla_rope((x @ p["wk_rope"].astype(dt))[:, :, None], pos,
                        cfg)[:, :, 0]
        new = {"latent": _lane_fill(jnp.concatenate([c_kv, k_pe], -1))}
        new_cache = None
        if cache is not None and "pt" in cache:
            views, new_cache = _paged_update(cache, new, cfg)
            o = _mla_absorbed(p, q_nope, q_pe, views["latent"], cfg,
                              causal=causal, kv_len=cache["len"] + s)
        elif cache is not None:
            idx = cache["len"]
            new_cache = dict(_append(cache, new, idx), len=idx + s)
            o = _mla_absorbed(p, q_nope, q_pe, new_cache["latent"], cfg,
                              causal=causal, kv_len=idx + s)
        else:
            o = _mla_expanded(p, q_nope, q_pe, new["latent"], cfg,
                              causal=causal)
            if return_cache:
                new_cache = dict(new, len=jnp.int32(s))
        out = jnp.einsum("bshv,hvd->bsd", o, p["wo"].astype(dt))
    if return_cache or cache is not None:
        return out, new_cache
    return out


def _lane_fill(x: jnp.ndarray) -> jnp.ndarray:
    """Zero-fill the last dim up to a multiple of :data:`LANES`."""
    pad = -x.shape[-1] % LANES
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x


def _split_latent(latent: jnp.ndarray, cfg: ModelConfig):
    """(..., lanes) cached latents -> (c_kv (..., r), k_pe (..., rope))."""
    r = cfg.kv_lora_rank
    return latent[..., :r], latent[..., r:r + cfg.qk_rope_dim]


def _mla_absorbed(p, q_nope, q_pe, latent, cfg: ModelConfig, *, causal: bool,
                  kv_len):
    """Attention in latent space over (B, T, lanes) cached latents:
    ``q_nope @ W_UK`` takes each query head into the latent, the scores are
    ``q_lat . c_kv + q_pe . k_pe``, and the output is ``(p . c_kv) @
    W_UV`` — the cache is never expanded to per-head keys and values.
    Scores and softmax in float32, as :func:`_sdpa`."""
    dt = q_nope.dtype
    f32 = jnp.float32
    c, k_pe = (a.astype(f32) for a in _split_latent(latent, cfg))
    q_lat = jnp.einsum("bshn,rhn->bshr", q_nope.astype(f32),
                       p["wk_b"].astype(f32))
    sc = (jnp.einsum("bshr,btr->bhst", q_lat, c)
          + jnp.einsum("bshp,btp->bhst", q_pe.astype(f32), k_pe)) \
        * cfg.softmax_scale
    b, s = q_nope.shape[:2]
    mask = _mask(b, s, c.shape[1], causal=causal, window=None, kv_len=kv_len)
    w = jax.nn.softmax(jnp.where(mask[:, None], sc, NEG_INF), axis=-1)
    o_lat = jnp.einsum("bhst,btr->bshr", w, c)
    return jnp.einsum("bshr,rhv->bshv", o_lat.astype(dt),
                      p["wv_b"].astype(dt))


def _mla_expanded(p, q_nope, q_pe, latent, cfg: ModelConfig, *,
                  causal: bool):
    """Attention over keys and values expanded per head from the latent:
    ``k = [c_kv @ W_UK, k_pe]``, ``v = c_kv @ W_UV``."""
    dt = q_nope.dtype
    c, k_pe = _split_latent(latent, cfg)
    k_pe = k_pe[:, :, None]
    k_nope = jnp.einsum("bsr,rhn->bshn", c, p["wk_b"].astype(dt))
    v = jnp.einsum("bsr,rhv->bshv", c, p["wv_b"].astype(dt))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:3] + k_pe.shape[-1:])],
        axis=-1)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    return _sdpa(q, k, v, causal=causal, window=None,
                 scale=cfg.softmax_scale)


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
