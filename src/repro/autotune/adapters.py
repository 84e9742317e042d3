"""Live-workload adapters: observed dispatch shapes -> tunable targets.

The recorder aggregates serving traffic into :class:`WorkloadKey`\\ s — a
(kind, prompt_len, batch, dtype) per distinct dispatch shape.  Each kernel
takes its own argument shapes, so someone has to say "a prefill of 16-token
prompts at batch 1 under THIS model is the causal flash-attention kernel at
(1, hq, 16, hd)".  That someone is this module: given the serving model and
engine configuration, :func:`serve_targets` maps each live key to the SIP
kernel the engine's hot path actually dispatches for it, with a
``make_args`` matching the observed shape.

Keys with no tunable kernel behind them (submit records, decode outside
paged serving) map to None and the service skips them.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import numpy as np

from repro.core.registry import Workload
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.paged_attention import ops as pg_ops
from repro.models import attention as A
from repro.models.config import ModelConfig
from repro.obs.recorder import WorkloadKey
from repro.serve.engine import ServeConfig


@dataclasses.dataclass(frozen=True)
class TuneTarget:
    """One tunable (kernel, workload) pair derived from a live key."""

    kernel: str
    workload: Workload


def _attn_args(b: int, hq: int, hkv: int, s: int, d: int, dtype: str):
    def make_args(rng: np.random.Generator) -> Sequence[np.ndarray]:
        dt = np.dtype(dtype)
        q = rng.standard_normal((b, hq, s, d)).astype(dt)
        k = rng.standard_normal((b, hkv, s, d)).astype(dt)
        v = rng.standard_normal((b, hkv, s, d)).astype(dt)
        return [q, k, v]
    return make_args


def _gather_args(p: int, ps: int, h: int, d: int, b: int, n: int, dtype: str):
    def make_args(rng: np.random.Generator) -> Sequence[np.ndarray]:
        store = rng.standard_normal((p, ps, h, d)).astype(np.dtype(dtype))
        pt = rng.integers(0, p, (b, n)).astype(np.int32)
        return [store, pt]
    return make_args


def serve_targets(cfg: ModelConfig, scfg: ServeConfig
                  ) -> Callable[[WorkloadKey], TuneTarget | None]:
    """The adapter for a serving deployment: live key -> tunable target.

    * ``prefill`` keys -> the flash-attention variant the model's SDPA path
      resolves (causal, ``cfg.window``), at the observed (batch, prompt_len)
      and the model's head geometry.  Under paged serving the engine
      prefills at page-rounded lengths, so the key's prompt_len is already
      the dispatched ``sq``.
    * ``decode`` keys -> the kernel paged serving's decode reads its
      cache with, at the geometry it dispatches: under ``cfg.use_pallas``
      the fused ``paged_decode_attention`` (kv heads x query group over
      the K/V stores, or MLA's one latent "head" of ``n_heads`` queries
      over the lane-filled latent store), else — and for padded heads,
      which keep the gathered view — the ``paged_gather`` kernel at the
      pool geometry; contiguous-mode decode has no SIP kernel on its path,
      so those keys are skipped.
    * anything else (``submit`` bookkeeping) -> None.
    """
    hd = cfg.hd
    ps = scfg.page_size
    n_slot_pages = -(-scfg.max_len // ps)
    num_pages = (scfg.num_pages if scfg.num_pages is not None
                 else scfg.capacity * n_slot_pages + 1)
    fused = cfg.use_pallas and A.kv_head_map(cfg) is None
    if cfg.mla:
        hkv = 1
        d = -(-(cfg.kv_lora_rank + cfg.qk_rope_dim) // A.LANES) * A.LANES
    else:
        hkv, d = cfg.n_kv_heads, hd

    def target_for(key: WorkloadKey) -> TuneTarget | None:
        if key.kind == "prefill" and key.prompt_len >= 1:
            name = fa_ops.ensure_registered(causal=True, window=cfg.window)
            make_args = _attn_args(key.batch, cfg.n_heads, cfg.n_kv_heads,
                                   key.prompt_len, hd, key.dtype)
            return TuneTarget(name, Workload(name=key.name,
                                             make_args=make_args,
                                             suites=("live",)))
        if key.kind == "decode" and scfg.paged and fused:
            make_args = pg_ops.decode_args(
                num_pages, ps, hkv, cfg.n_heads // hkv, d, key.batch,
                n_slot_pages, shared_kv=cfg.mla, dtype=key.dtype)
            return TuneTarget(pg_ops.DECODE_NAME, Workload(
                name=key.name, make_args=make_args, suites=("live",)))
        if key.kind == "decode" and scfg.paged:
            make_args = _gather_args(num_pages, ps, cfg.n_kv_heads, hd,
                                     key.batch, n_slot_pages, key.dtype)
            return TuneTarget(pg_ops.NAME, Workload(name=key.name,
                                                    make_args=make_args,
                                                    suites=("live",)))
        return None

    return target_for
