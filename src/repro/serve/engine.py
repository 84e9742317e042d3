"""Serving engines over the SIP-tuned model stack.

Two engines share the jitted prefill/decode step functions (models/model.py —
the same functions the dry-run lowers, so schedules cached by SIP benefit
serving directly):

* :class:`Engine` — static batch: one prefill over (B, S) prompts, lockstep
  decode until every row stops.  Kept as the differential-correctness
  reference (single-request generation) and the throughput baseline.
* :class:`ContinuousEngine` — continuous batching: a FIFO request queue with
  slot-based admission into a fixed-capacity decode batch.  Each arriving
  request is prefilled alone (exact prompt length, batch 1), its KV/SSM cache
  segment is spliced into a free slot (models/model.py per-slot helpers), and
  all occupied slots decode in lockstep — finished slots are evicted and
  refilled from the queue without stalling the batch.  Per-request stop
  (eos / max tokens), streaming emission via ``on_token``, and a stats
  surface (queue depth, slot occupancy, prefill/decode split, tokens/s)
  built on :mod:`repro.obs` — counters/gauges/latency histograms in a
  metrics registry, prefill/decode spans on the active tracer, and an
  optional live-workload recorder (see :class:`ContinuousEngine`).

  With ``ServeConfig(paged=True)`` the continuous engine swaps the per-slot
  contiguous cache segments for a paged KV store (``repro.serve.pages``):
  attention cache traffic goes through per-slot page tables over a shared
  page pool, admission reserves worst-case pages up front (decode never
  allocates), identical prompt prefixes share pages read-only through a
  content-hashed prefix cache, and long prompts optionally prefill in
  fixed-size chunks interleaved with decode (``prefill_chunk``).  Greedy
  outputs stay token-identical to the static reference engine —
  tests/test_serve_paged.py holds every paged mode to that.

  With a ``mesh`` the continuous engine serves tensor-parallel: params and
  every cache leaf (per-slot segments or the paged flat store, whose
  head axis is the natural mesh seam — the host-side page tables are
  shard-invariant page ids) carry NamedShardings, and prefill / chunked
  prefill / lockstep decode dispatch sharded.  TP-eligible attention configs
  (``dist.tp.tp_eligible``) run the manual shard_map path — the forward in
  one ``shard_map`` body with exactly two explicit psums per layer,
  optionally int8-compressed (``ServeConfig.compressed_collectives``) —
  and everything else falls back to GSPMD under ``partition.SERVE_RULES``.
  Greedy sharded output is token-identical to the 1-device engine
  (tests/test_sharding_multidevice.py::serve_sharded holds both cache
  layouts to that at two mesh shapes).

Kernel resolution happens at trace time, so wrap serving in
``repro.core.registry.schedule_cache(path)`` to serve SIP-tuned schedules on
the hot path (see launch/serve.py).  Registry handles are late-binding: a
scope entered before engine construction is honored, and tuning that bumps
``ScheduleCache.version`` mid-flight re-resolves on the next trace.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.registry import active_schedule_cache
from repro.dist import partition, tp
from repro.dist.compat import shard_map
from repro.models import model as M
from repro.models import modules as nn
from repro.models.config import ModelConfig
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.recorder import WorkloadRecorder
from repro.serve.pages import PagePool, PagesExhausted, PrefixCache
from repro.serve.slots import SlotPool

#: paged serving supports the attention families; SSM/hybrid conv+state
#: caches and enc-dec cross context are dense per-slot state, and SWA ring
#: buffers already bound cache size by the window
PAGED_FAMILIES = ("dense", "moe", "vlm")


@dataclasses.dataclass
class ServeConfig:
    max_len: int = 256              # per-slot cache length (prompt + new)
    temperature: float = 0.0        # 0 = greedy
    seed: int = 0
    capacity: int = 8               # decode-batch slots (ContinuousEngine)
    # ---- paged KV cache (ContinuousEngine; see repro.serve.pages) --------
    paged: bool = False             # page the KV store instead of per-slot
                                    # contiguous max_len segments
    page_size: int = 16             # tokens per cache page
    num_pages: int | None = None    # page budget incl. the trash page;
                                    # None = capacity * ceil(max_len/page_size)
                                    # + 1 (contiguous-equivalent memory)
    prefill_chunk: int | None = None  # split prompts longer than this into
                                    # fixed-size chunks interleaved with
                                    # decode (bounds TTFT under long arrivals
                                    # AND prefill recompiles); None = whole-
                                    # prompt prefill dispatches
    prefix_cache: bool = True       # content-hashed prefix sharing (paged)
    admission: str = "queue"        # "queue": wait for pages/slots;
                                    # "reject": submit raises PagesExhausted
                                    # unless the request can start NOW
    # ---- tensor-parallel serving (ContinuousEngine(mesh=...)) ------------
    tp_mode: str = "auto"           # "auto": manual shard_map TP when the
                                    # config is eligible (dist.tp.tp_eligible)
                                    # else GSPMD; "shard_map"/"gspmd" force a
                                    # path (shard_map raises if ineligible)
    compressed_collectives: bool = False  # int8-compress the two per-layer
                                    # decode-seam psums (shard_map path only;
                                    # bounded error, NOT token-exact)
    compress_block: int = 64        # quantization block for compressed seams


def resolve_tp_path(cfg: ModelConfig, mesh, tp_mode: str = "auto",
                    compressed: bool = False) -> tuple[str, str]:
    """Pick the sharded execution path for serving ``cfg`` on ``mesh`` per
    ``tp_mode`` (see :mod:`repro.dist.tp` for the eligibility rationale).
    Returns ``(path, reason)``."""
    if "model" not in mesh.axis_names:
        raise ValueError(f"serving mesh needs a 'model' axis, got "
                         f"{mesh.axis_names}")
    ok, reason = tp.tp_eligible(cfg, mesh.shape["model"])
    if tp_mode == "shard_map":
        if not ok:
            raise ValueError(f"tp_mode='shard_map' but {reason}")
        path = "shard_map"
    elif tp_mode == "gspmd":
        path = "gspmd"
    elif tp_mode == "auto":
        path = "shard_map" if ok else "gspmd"
    else:
        raise ValueError(f"tp_mode must be 'auto'/'shard_map'/'gspmd', "
                         f"got {tp_mode!r}")
    if compressed and path != "shard_map":
        raise ValueError(f"compressed_collectives needs the shard_map TP "
                         f"path ({reason})")
    return path, reason


def param_shardings(cfg: ModelConfig, mesh, tp_path: str):
    """Where a serving engine on ``mesh`` keeps each parameter: the manual
    TP layout on the shard_map path, ``SERVE_RULES`` on the GSPMD path."""
    paxes = M.param_logical_axes(cfg)
    if tp_path == "shard_map":
        return tp.tp_shardings(paxes, mesh)
    return partition.tree_shardings(
        paxes, mesh, sds_tree=nn.unwrap(M.init_lm_shapes(
            jax.random.PRNGKey(0), cfg)), rules=partition.SERVE_RULES)


def init_params(key, cfg: ModelConfig, mesh=None, tp_mode: str = "auto"):
    """Random serving parameters made in place: on the default device, or
    with ``mesh`` directly in the shards the engine will serve them from.
    One jitted init, so no device ever holds a whole sharded model."""
    def init(k):
        return nn.unwrap(M.init_lm(k, cfg))
    if mesh is None:
        return jax.jit(init)(key)
    path, _ = resolve_tp_path(cfg, mesh, tp_mode)
    return jax.jit(init, out_shardings=param_shardings(cfg, mesh, path))(key)


class Engine:
    """Static-batch engine: one prefill, lockstep decode, whole batch stops
    together.  The B=1 case is the correctness reference for the
    continuous-batching engine."""

    def __init__(self, params, cfg: ModelConfig,
                 scfg: ServeConfig | None = None):
        self.params = params
        self.cfg = cfg
        self.scfg = scfg = ServeConfig() if scfg is None else scfg
        self._prefill = jax.jit(functools.partial(
            M.prefill, cfg=cfg, max_len=scfg.max_len))
        # donate the cache buffers: decode updates them in place instead of
        # copying the full KV tree every step
        self._decode = jax.jit(functools.partial(
            _decode_sample, cfg=cfg, temperature=scfg.temperature),
            donate_argnums=(1,))
        self.stats: dict[str, Any] = {"prefill_s": 0.0, "decode_s": 0.0,
                                      "tokens_out": 0}

    def generate(self, prompts: np.ndarray, max_new_tokens: int,
                 extra_inputs: dict[str, Any] | None = None,
                 eos_id: int | None = None) -> np.ndarray:
        """prompts: (B, S) int32 -> (B, <=max_new_tokens) int32."""
        b = prompts.shape[0]
        inputs = {"tokens": jnp.asarray(prompts, jnp.int32)}
        if extra_inputs:
            inputs.update(extra_inputs)
        key = jax.random.PRNGKey(self.scfg.seed)

        t0 = time.perf_counter()
        logits, caches = self._prefill(self.params, inputs)
        jax.block_until_ready(logits)
        self.stats["prefill_s"] += time.perf_counter() - t0

        out = []
        token = _pick(logits, self.scfg.temperature, key)
        done = np.zeros(b, bool)
        t0 = time.perf_counter()
        for i in range(max_new_tokens):
            out.append(np.asarray(token))
            if eos_id is not None:
                done |= (out[-1] == eos_id)
                if done.all():
                    break
            key, sub = jax.random.split(key)
            token, caches = self._decode(self.params, caches, token, key=sub)
        jax.block_until_ready(token)
        self.stats["decode_s"] += time.perf_counter() - t0
        self.stats["tokens_out"] += int(np.size(out))
        return np.stack(out, axis=1)


def static_batches(prompts, budgets, capacity: int):
    """The static-batch baseline's serving plan: arrival-order chunks of
    ``capacity``, prompts left-padded to the batch max, each batch decoding
    to its largest budget.  Yields ``(padded_prompts, new_tokens, indices)``;
    shared by the traffic driver and the throughput benchmark so the
    baseline semantics exist exactly once."""
    for s in range(0, len(prompts), capacity):
        idxs = list(range(s, min(s + capacity, len(prompts))))
        plen = max(len(prompts[j]) for j in idxs)
        padded = np.zeros((len(idxs), plen), np.int32)
        for r, j in enumerate(idxs):
            padded[r, plen - len(prompts[j]):] = prompts[j]
        yield padded, max(budgets[j] for j in idxs), idxs


# ======================================================= continuous batching
@dataclasses.dataclass
class Request:
    """One generation request.  ``prompt`` is an unbatched (S,) token vector;
    ``extra`` holds unbatched per-request extra inputs (``enc_embeds`` for
    enc-dec archs, ``embeds`` for VLM embedding prompts) — the engine adds
    the batch axis."""

    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    eos_id: int | None = None
    extra: dict[str, np.ndarray] | None = None
    # -- filled by the engine ------------------------------------------------
    tokens: list[int] = dataclasses.field(default_factory=list)
    submitted_at: float = 0.0
    admitted_at: float | None = None
    finished_at: float | None = None

    @property
    def done(self) -> bool:
        return self.finished_at is not None

    @property
    def output(self) -> np.ndarray:
        return np.asarray(self.tokens, np.int32)


#: the engine's cumulative counters; ``stats`` assembles them in this order
_STAT_KEYS = ("prefill_s", "decode_s", "tokens_out", "prefill_tokens",
              "submitted", "admitted", "completed", "steps", "decode_steps",
              "occupancy_sum", "queue_depth_sum", "prefill_compiles",
              "prefix_hits", "prefix_tokens_saved", "chunk_steps",
              "schedule_swaps")


@dataclasses.dataclass
class _ChunkTask:
    """A slot mid chunked-prefill: the first ``pos`` prompt tokens are
    already in its pages (shared-prefix pages and/or completed chunks)."""

    req: Request
    slot: int
    pos: int


def _rep(tree):
    """Full-rank replicated PartitionSpecs for a pytree (shard_map in_specs
    for host-owned operands: tokens, page tables, masks, scalars)."""
    return jax.tree.map(lambda x: P(*([None] * jnp.ndim(x))), tree)


def _shape_key(req: Request) -> tuple:
    """Prefill-coalescing key: requests with equal keys compile and batch
    together."""
    return (len(req.prompt),
            tuple(sorted((k, np.asarray(v).shape)
                         for k, v in (req.extra or {}).items())))


def _ratio(num: float, den: float) -> float:
    """A derived rate that is well-defined 0.0 (never inf/NaN, never a
    division error) for zero-step/zero-token runs."""
    return num / den if den > 0 else 0.0


class ContinuousEngine:
    """Continuous-batching engine (see module docstring).

    One :meth:`step` = admit-from-queue (prefill each admitted request at its
    exact prompt length, splice into its slot, emit its first token) + one
    lockstep decode over the slot batch.  :meth:`run` steps until drained.
    Greedy decoding is token-identical to single-request
    ``Engine.generate`` for every request, whatever the arrival order —
    tests/test_serve_continuous.py holds the engine to that.

    Telemetry: every counter behind :attr:`stats` / :meth:`metrics` lives in
    a :class:`~repro.obs.metrics.MetricsRegistry` (``obs`` — engine-local by
    default so concurrent engines never share counters; pass one to fold a
    serve run into a wider snapshot), alongside TTFT / inter-token-latency /
    dispatch-time histograms and occupancy / queue-depth gauges.  Prefill
    and decode dispatches are traced as spans on the active
    ``repro.obs.trace`` tracer, and an optional :class:`WorkloadRecorder`
    logs the live (shape, dtype, occupancy) mix for offline tuning.
    """

    def __init__(self, params, cfg: ModelConfig,
                 scfg: ServeConfig | None = None,
                 example_extra: dict[str, np.ndarray] | None = None,
                 on_token: Callable[[Request, int], None] | None = None,
                 obs: obs_metrics.MetricsRegistry | None = None,
                 recorder: WorkloadRecorder | None = None,
                 mesh=None):
        cfg.validate()
        self.params = params
        self.cfg = cfg
        self.scfg = scfg = ServeConfig() if scfg is None else scfg
        self.capacity = scfg.capacity
        # tensor-parallel serving: with a mesh, params and every cache leaf
        # carry NamedShardings and the model dispatches run sharded — the
        # manual shard_map path when the config is TP-eligible (explicit
        # per-layer psums, optionally int8-compressed), GSPMD otherwise
        self.mesh = mesh
        self.tp_path: str | None = None
        self.tp_reason = ""
        if mesh is not None:
            self.tp_path, self.tp_reason = resolve_tp_path(
                cfg, mesh, scfg.tp_mode, scfg.compressed_collectives)
        elif scfg.compressed_collectives:
            raise ValueError("compressed_collectives requires a serving mesh "
                             "(the seams only exist on the shard_map path)")
        self.on_token = on_token
        self.obs = obs if obs is not None else obs_metrics.MetricsRegistry()
        self.recorder = recorder
        self.pool = SlotPool(scfg.capacity)
        # conv-state shapes only stabilize once the prompt covers the conv
        # receptive field — shorter prompts would prefill a cache segment that
        # cannot be spliced into the fixed-shape slot batch
        self._min_prompt = (cfg.conv_width - 1
                            if cfg.family in ("ssm", "hybrid") else 1)
        s0 = min(max(8, self._min_prompt), scfg.max_len)
        example_inputs = {"tokens": np.zeros((1, s0), np.int32)}
        if example_extra:
            example_inputs.update(
                {k: np.asarray(v)[None] for k, v in example_extra.items()})
        self._example_extra_shapes = {
            k: tuple(np.asarray(v).shape) for k, v in (example_extra or {}).items()}
        self.paged = scfg.paged
        if self.paged:
            if cfg.family not in PAGED_FAMILIES:
                raise ValueError(
                    f"paged serving supports {PAGED_FAMILIES}, not "
                    f"{cfg.family!r} (its decode state is dense per-slot)")
            if scfg.admission not in ("queue", "reject"):
                raise ValueError(f"admission must be 'queue' or 'reject', "
                                 f"got {scfg.admission!r}")
            if scfg.prefill_chunk is not None and scfg.prefill_chunk < 1:
                raise ValueError(f"prefill_chunk must be >= 1, got "
                                 f"{scfg.prefill_chunk}")
            ps = scfg.page_size
            self._n_slot_pages = -(-scfg.max_len // ps)
            num_pages = (scfg.num_pages if scfg.num_pages is not None
                         else scfg.capacity * self._n_slot_pages + 1)
            # page 0 is the trash page: a freed/idle slot's zeroed page-table
            # row makes its masked decode scatters land there harmlessly
            self.pages = PagePool(num_pages, ps, obs=self.obs)
            self.prefix = (PrefixCache(self.pages, obs=self.obs)
                           if scfg.prefix_cache else None)
            self.caches, self._axes = M.alloc_paged_caches(
                params, cfg, scfg.capacity, scfg.max_len, ps, num_pages,
                example_inputs)
            # host-side page tables, (capacity, n_slot_pages) int32 — passed
            # into every paged dispatch; a slot's row is zeroed while free
            self._pt = np.zeros((scfg.capacity, self._n_slot_pages), np.int32)
            self._slot_pages: dict[int, list[int]] = {}
            self._chunk_tasks: collections.deque[_ChunkTask] = \
                collections.deque()
            self._prefilling: set[int] = set()
        else:
            self.caches, self._axes = M.alloc_slot_caches(
                params, cfg, scfg.capacity, scfg.max_len, example_inputs)
        if mesh is not None:
            self._shard_state()
        self._make_dispatchers()
        # schedule hot-swap: kernel handles are late-binding, but jax.jit
        # memoizes traces by shape — a ScheduleCache version bump alone never
        # reaches an already-traced dispatch.  The engine snapshots the
        # active store's version here and _maybe_refresh_schedules() rebuilds
        # the jit wrappers when it moves, so the NEXT trace re-resolves every
        # kernel from the updated store (restart-free promotion; see
        # repro.autotune).
        self._sched_cache = active_schedule_cache()
        self._sched_version = (self._sched_cache.version
                               if self._sched_cache is not None else 0)
        self.tokens = np.zeros(scfg.capacity, np.int32)   # next decode inputs
        self._key = jax.random.PRNGKey(scfg.seed)
        self._uid = 0
        self._prefill_shapes_seen: set[tuple] = set()
        self._c = {k: self.obs.counter(f"serve.{k}") for k in _STAT_KEYS}
        self._g_occupancy = self.obs.gauge("serve.occupancy")
        self._g_queue_depth = self.obs.gauge("serve.queue_depth")
        if self.paged:
            self._g_page_occ = self.obs.gauge("serve.page_occupancy")
        self._h_ttft = self.obs.histogram("serve.ttft_s")
        self._h_itl = self.obs.histogram("serve.inter_token_s")
        self._h_prefill = self.obs.histogram("serve.prefill_call_s")
        self._h_decode = self.obs.histogram("serve.decode_step_s")
        self._last_emit: dict[int, float] = {}   # uid -> last token time

    # ------------------------------------------------------- sharded serving
    def _shard_state(self) -> None:
        """Move params and the freshly allocated slot/page caches onto the
        serving mesh.  Admission never materializes an unsharded cache after
        this: every dispatcher pins its cache outputs back to these
        shardings, and splicing (insert/evict/set_len) runs on the sharded
        buffers in place."""
        mesh, cfg = self.mesh, self.cfg
        caxes = M.serve_cache_axes(cfg, self._axes)
        self._grp_axes = M.cache_logical_axes(cfg)
        pshard = param_shardings(cfg, mesh, self.tp_path)
        if self.tp_path == "shard_map":
            self._pspecs = tp.tp_specs(M.param_logical_axes(cfg))
            self._cspecs = tp.tp_specs(caxes)
            self._grp_specs = tp.tp_specs(self._grp_axes)
            cshard = tp.tp_shardings(caxes, mesh)
        else:
            cshard = partition.tree_shardings(caxes, mesh,
                                              sds_tree=self.caches,
                                              rules=partition.SERVE_RULES)
        # a no-op for params made by init_params on this mesh
        self.params = jax.device_put(self.params, pshard)
        self.caches = jax.device_put(self.caches, cshard)
        self._cache_shardings = cshard

    def _seams(self):
        """The manual-TP scope every shard_map body runs under."""
        return tp.tp_context("model",
                             compressed=self.scfg.compressed_collectives,
                             block=self.scfg.compress_block)

    def _pin_slot_caches(self, caches):
        """Constrain a slot/page cache tree back to the engine's shardings
        (inside a traced fn) so splice outputs keep the mesh layout and
        decode's donation reuses the same sharded buffers."""
        return jax.tree.map(jax.lax.with_sharding_constraint, caches,
                            self._cache_shardings)

    def _pin_group_caches(self, caches):
        """Same, for a group-sized prefill cache (GSPMD path; trace-time
        shapes drive the divisibility fallback per leaf)."""
        return jax.tree.map(
            lambda ax, x: jax.lax.with_sharding_constraint(
                x, partition.named_sharding(ax, self.mesh, shape=x.shape,
                                            rules=partition.SERVE_RULES)),
            self._grp_axes, caches, is_leaf=partition._is_axes_leaf)

    def _build_prefill(self, max_len: int):
        """One prefill dispatcher at ``max_len`` for the active path —
        single-device, GSPMD (traced under mesh_rules so the model's shard
        constraints activate), or manual shard_map TP (the whole forward in
        one shard_map body, seams reduced via tp_allreduce)."""
        cfg, mesh = self.cfg, self.mesh
        if mesh is None:
            return jax.jit(functools.partial(M.prefill, cfg=cfg,
                                             max_len=max_len))
        if self.tp_path == "shard_map":
            def tp_prefill(params, inputs):
                def body(p, i):
                    with self._seams():
                        return M.prefill(p, i, cfg, max_len=max_len)
                return shard_map(
                    body, mesh=mesh, in_specs=(self._pspecs, _rep(inputs)),
                    out_specs=(P(), self._grp_specs),
                    check_vma=False)(params, inputs)
            return jax.jit(tp_prefill)

        def gs_prefill(params, inputs):
            with partition.mesh_rules(mesh, partition.SERVE_RULES):
                logits, caches = M.prefill(params, inputs, cfg,
                                           max_len=max_len)
                return logits, self._pin_group_caches(caches)
        return jax.jit(gs_prefill)

    def _make_dispatchers(self) -> None:
        """(Re)create the jitted step functions.  Called at construction and
        again on schedule hot-swap: fresh jax.jit wrappers mean fresh trace
        caches, so every kernel re-resolves against the current
        ScheduleCache contents on its next dispatch."""
        cfg, scfg = self.cfg, self.scfg
        if self.mesh is not None and self.tp_path == "shard_map":
            self._make_tp_dispatchers()
            return
        if self.mesh is not None:
            self._make_gspmd_dispatchers()
            return
        if self.paged:
            # paged prefill compiles once per page-rounded prompt length (or
            # per chunk shape) — these jits are keyed by that rounded length
            self._prefill_by_len: dict[int, Any] = {}
            self._decode = jax.jit(functools.partial(
                _decode_sample_paged, cfg=cfg, temperature=scfg.temperature),
                donate_argnums=(1,))
            self._insert_pages = jax.jit(
                lambda caches, grp, slots, pages: M.insert_pages(
                    caches, grp, slots, pages, self._axes),
                donate_argnums=(0,))
            self._set_len = jax.jit(
                lambda caches, slot, value: M.set_slot_lens(
                    caches, slot, value, self._axes),
                donate_argnums=(0,))
            self._chunk = jax.jit(functools.partial(
                M.prefill_chunk, cfg=cfg, axes=self._axes),
                donate_argnums=(1,))
        else:
            self._prefill = jax.jit(functools.partial(
                M.prefill, cfg=cfg, max_len=scfg.max_len))
            # the slot batch is donated through decode and insert, so the
            # steady state mutates ONE cache allocation instead of copying
            # the full KV/SSM tree every step/admission
            self._decode = jax.jit(functools.partial(
                _decode_sample, cfg=cfg, temperature=scfg.temperature),
                donate_argnums=(1,))
            self._insert = jax.jit(
                lambda caches, grp, slots: M.insert_slots(caches, grp, slots,
                                                          self._axes),
                donate_argnums=(0,))

    def _make_gspmd_dispatchers(self) -> None:
        """Sharded dispatchers, GSPMD path: the existing step functions
        traced under ``mesh_rules(SERVE_RULES)`` (activating the model's
        ``shard`` constraints) with cache outputs pinned to the engine's
        shardings — the compiler places the collectives."""
        cfg, scfg, mesh = self.cfg, self.scfg, self.mesh
        rules = partition.SERVE_RULES
        if self.paged:
            self._prefill_by_len = {}

            def gs_decode(params, caches, token, pt, active, *, key):
                with partition.mesh_rules(mesh, rules):
                    tok, caches = _decode_sample_paged(
                        params, caches, token, pt, active, cfg=cfg,
                        temperature=scfg.temperature, key=key)
                    return tok, self._pin_slot_caches(caches)
            self._decode = jax.jit(gs_decode, donate_argnums=(1,))
            self._insert_pages = jax.jit(
                lambda caches, grp, slots, pages: self._pin_slot_caches(
                    M.insert_pages(caches, grp, slots, pages, self._axes)),
                donate_argnums=(0,))
            self._set_len = jax.jit(
                lambda caches, slot, value: self._pin_slot_caches(
                    M.set_slot_lens(caches, slot, value, self._axes)),
                donate_argnums=(0,))

            def gs_chunk(params, caches, tokens, pt_row, slot, n_valid,
                         embeds=None):
                with partition.mesh_rules(mesh, rules):
                    last, caches = M.prefill_chunk(
                        params, caches, tokens, pt_row, slot, n_valid,
                        cfg=cfg, axes=self._axes, embeds=embeds)
                    return last, self._pin_slot_caches(caches)
            self._chunk = jax.jit(gs_chunk, donate_argnums=(1,))
        else:
            self._prefill = self._build_prefill(scfg.max_len)

            def gs_decode(params, caches, token, *, key):
                with partition.mesh_rules(mesh, rules):
                    tok, caches = _decode_sample(
                        params, caches, token, cfg=cfg,
                        temperature=scfg.temperature, key=key)
                    return tok, self._pin_slot_caches(caches)
            self._decode = jax.jit(gs_decode, donate_argnums=(1,))
            self._insert = jax.jit(
                lambda caches, grp, slots: self._pin_slot_caches(
                    M.insert_slots(caches, grp, slots, self._axes)),
                donate_argnums=(0,))

    def _make_tp_dispatchers(self) -> None:
        """Sharded dispatchers, manual shard_map TP path: each model forward
        runs as one shard_map body under ``tp_context`` — heads/kv-heads and
        the MLP hidden dim are mesh-local, and the only collectives are the
        two explicit per-layer ``tp_allreduce`` seams (exact psum, or
        ``compressed_psum`` when ``scfg.compressed_collectives``).  Sampling
        stays outside the shard_map on the replicated logits.  Cache
        splicing has no seam dimension contraction, so it stays a plain
        GSPMD jit pinned to the slot-cache shardings."""
        cfg, scfg, mesh = self.cfg, self.scfg, self.mesh
        pspecs, cspecs = self._pspecs, self._cspecs
        if self.paged:
            self._prefill_by_len = {}

            def tp_decode(params, caches, token, pt, active, *, key):
                def body(p, c, t, ptt, act):
                    with self._seams():
                        return M.decode_step(p, c, t, cfg, pt=ptt, active=act)
                logits, caches = shard_map(
                    body, mesh=mesh,
                    in_specs=(pspecs, cspecs, _rep(token), _rep(pt),
                              _rep(active)),
                    out_specs=(P(), cspecs), check_vma=False)(
                        params, caches, token, pt, active)
                return _pick(logits, scfg.temperature, key), caches
            self._decode = jax.jit(tp_decode, donate_argnums=(1,))
            self._insert_pages = jax.jit(
                lambda caches, grp, slots, pages: self._pin_slot_caches(
                    M.insert_pages(caches, grp, slots, pages, self._axes)),
                donate_argnums=(0,))
            self._set_len = jax.jit(
                lambda caches, slot, value: self._pin_slot_caches(
                    M.set_slot_lens(caches, slot, value, self._axes)),
                donate_argnums=(0,))

            def tp_chunk(params, caches, tokens, pt_row, slot, n_valid,
                         embeds=None):
                args = (params, caches, tokens, pt_row, slot, n_valid)
                specs = (pspecs, cspecs, _rep(tokens), _rep(pt_row), P(), P())
                if embeds is not None:
                    args += (embeds,)
                    specs += (_rep(embeds),)

                def body(p, c, t, ptr, s, nv, *e):
                    with self._seams():
                        return M.prefill_chunk(
                            p, c, t, ptr, s, nv, cfg=cfg, axes=self._axes,
                            embeds=e[0] if e else None)
                return shard_map(body, mesh=mesh, in_specs=specs,
                                 out_specs=(P(), cspecs),
                                 check_vma=False)(*args)
            self._chunk = jax.jit(tp_chunk, donate_argnums=(1,))
        else:
            self._prefill = self._build_prefill(scfg.max_len)

            def tp_decode(params, caches, token, *, key):
                def body(p, c, t):
                    with self._seams():
                        return M.decode_step(p, c, t, cfg)
                logits, caches = shard_map(
                    body, mesh=mesh, in_specs=(pspecs, cspecs, _rep(token)),
                    out_specs=(P(), cspecs), check_vma=False)(
                        params, caches, token)
                return _pick(logits, scfg.temperature, key), caches
            self._decode = jax.jit(tp_decode, donate_argnums=(1,))
            self._insert = jax.jit(
                lambda caches, grp, slots: self._pin_slot_caches(
                    M.insert_slots(caches, grp, slots, self._axes)),
                donate_argnums=(0,))

    def _maybe_refresh_schedules(self) -> None:
        """Pick up ScheduleCache changes without a restart: when the store
        the engine was constructed under has a newer version (an autotune
        promotion, or a tuning session sharing the store), drop every traced
        dispatch and rebuild, so subsequent prefills/decodes trace against
        the new schedules.  KV caches, page tables, slots and in-flight
        requests are untouched — only the compiled functions turn over.

        Polled before EVERY dispatch (admission prefill, chunked prefill,
        decode), not just at the top of :meth:`step`: commits can land
        mid-step — an autotune thread promoting between the admission
        prefill and the decode dispatch, or an ``on_token`` callback
        committing during emission — and a top-of-step-only poll would serve
        the rest of that step (and any dispatch the step path skips) on
        stale schedules."""
        cache = self._sched_cache
        if cache is None or not cache.changed_since(self._sched_version):
            return
        self._sched_version = cache.version
        self._c["schedule_swaps"].inc()
        # compile accounting restarts with the trace caches
        self._prefill_shapes_seen.clear()
        self._make_dispatchers()
        obs_trace.instant("serve.schedule_swap", version=cache.version)

    # -------------------------------------------------------------- ingress
    def submit(self, prompt: np.ndarray, max_new_tokens: int,
               eos_id: int | None = None,
               extra: dict[str, np.ndarray] | None = None) -> Request:
        """Enqueue one request; returns its :class:`Request` handle."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got "
                             f"{max_new_tokens}")
        if len(prompt) < self._min_prompt:
            raise ValueError(
                f"{self.cfg.family} prompts need >= {self._min_prompt} "
                f"tokens (conv receptive field), got {len(prompt)}")
        total = len(prompt) + max_new_tokens
        if not self.paged:
            if total > self.scfg.max_len:
                raise ValueError(
                    f"prompt ({len(prompt)}) + max_new_tokens "
                    f"({max_new_tokens}) exceeds max_len "
                    f"({self.scfg.max_len})")
        else:
            # paged admission is a CAPACITY check, not a length check: the
            # hard bound is the per-slot page table (page-rounded, so a few
            # tokens past max_len that still fit the last page are fine);
            # whether the request can start is a question about free pages,
            # answered per the admission policy
            ps = self.pages.page_size
            bound = self._n_slot_pages * ps
            if total > bound:
                raise ValueError(
                    f"prompt ({len(prompt)}) + max_new_tokens "
                    f"({max_new_tokens}) exceeds the per-slot page table "
                    f"({self._n_slot_pages} pages x {ps} = {bound} tokens)")
            worst = -(-total // ps)
            if worst > self.pages.usable_pages:
                raise ValueError(
                    f"request needs {worst} pages but the pool has only "
                    f"{self.pages.usable_pages} usable — it could never be "
                    f"admitted; raise num_pages")
            if self.scfg.admission == "reject" and not self._admissible(worst):
                raise PagesExhausted(
                    f"request needs {worst} pages now but "
                    f"free={self.pages.free_pages} + evictable="
                    f"{self.prefix.evictable_pages if self.prefix else 0}, "
                    f"free_slots={self.pool.free_slots}, "
                    f"queued={self.pool.queue_depth} — resubmit later or "
                    f"serve with admission='queue'")
        got = {k: tuple(np.asarray(v).shape) for k, v in (extra or {}).items()}
        for k, shape in self._example_extra_shapes.items():
            # seq-varying extras (VLM embeds) follow the prompt; fixed-shape
            # extras (enc-dec context) must match the engine's allocation
            if k == "enc_embeds" and got.get(k) != shape:
                raise ValueError(f"extra {k!r} must have shape {shape}, "
                                 f"got {got.get(k)}")
        if "embeds" in got and got["embeds"][0] != len(prompt):
            # prefill advances the cache by the EMBEDS length, so a mismatch
            # would silently break the max_len/position accounting above
            raise ValueError(f"extra 'embeds' length {got['embeds'][0]} "
                             f"must match the prompt length {len(prompt)}")
        req = Request(uid=self._uid, prompt=prompt,
                      max_new_tokens=max_new_tokens, eos_id=eos_id,
                      extra=extra, submitted_at=time.perf_counter())
        self._uid += 1
        self._c["submitted"].inc()
        if self.recorder is not None:
            self.recorder.record("submit", prompt_len=len(prompt),
                                 dtype=self.cfg.dtype,
                                 new_tokens=max_new_tokens,
                                 occupancy=self.pool.occupancy,
                                 queue_depth=self.pool.queue_depth)
        self.pool.submit(req)
        return req

    # ----------------------------------------------------------------- step
    def step(self) -> list[Request]:
        """Admit + prefill waiting requests into free slots, then run one
        lockstep decode over the occupied batch.  Returns requests that
        finished during this step."""
        self._maybe_refresh_schedules()
        finished: list[Request] = []
        if self.paged:
            self._admit_paged(finished)
            if self._chunk_tasks:
                self._chunk_step(finished)
            self._decode_paged(finished)
            self._g_page_occ.set(_ratio(self.pages.used_pages,
                                        self.pages.usable_pages))
        else:
            groups: dict[Any, list[tuple[int, Request]]] = {}
            for slot, req in self.pool.admit():
                # coalesce same-shape admissions into one batched prefill —
                # the per-row math is identical to batch-1, at one dispatch
                # per group
                groups.setdefault(_shape_key(req), []).append((slot, req))
            for group in groups.values():
                self._admit_group(group, finished)
            if self.pool.occupancy:
                self._maybe_refresh_schedules()
                occ = self.pool.occupancy
                t0 = time.perf_counter()
                with obs_trace.span("serve.decode", occupancy=occ):
                    self._key, sub = jax.random.split(self._key)
                    tok, self.caches = self._decode(
                        self.params, self.caches, jnp.asarray(self.tokens),
                        key=sub)
                    tok = np.asarray(tok)
                dt = time.perf_counter() - t0
                self._c["decode_s"].inc(dt)
                self._c["decode_steps"].inc()
                self._h_decode.record(dt)
                if self.recorder is not None:
                    self.recorder.record("decode", batch=self.capacity,
                                         dtype=self.cfg.dtype, occupancy=occ,
                                         queue_depth=self.pool.queue_depth)
                for slot, req in list(self.pool.held()):
                    self.tokens[slot] = int(tok[slot])
                    self._emit(slot, req, int(tok[slot]), finished)
        self._c["steps"].inc()
        self._c["occupancy_sum"].inc(self.pool.occupancy)
        self._c["queue_depth_sum"].inc(self.pool.queue_depth)
        self._g_occupancy.set(self.pool.occupancy)
        self._g_queue_depth.set(self.pool.queue_depth)
        return finished

    def run(self, max_steps: int | None = None) -> dict[int, np.ndarray]:
        """Step until queue and slots drain; returns {uid: generated tokens}."""
        out: dict[int, np.ndarray] = {}
        steps = 0
        while not self.pool.idle:
            for req in self.step():
                out[req.uid] = req.output
            steps += 1
            if max_steps is not None and steps > max_steps:
                raise RuntimeError(f"engine not drained after {max_steps} "
                                   f"steps ({self.pool!r})")
        return out

    # ------------------------------------------------------------ internals
    def _admit_group(self, group: list[tuple[int, Request]],
                     finished: list[Request]) -> None:
        self._maybe_refresh_schedules()
        t0 = time.perf_counter()
        slots = np.asarray([s for s, _ in group], np.int32)
        prompts = np.stack([r.prompt for _, r in group])
        inputs = {"tokens": jnp.asarray(prompts)}
        for k in (group[0][1].extra or {}):
            inputs[k] = jnp.asarray(
                np.stack([np.asarray(r.extra[k]) for _, r in group]))
        if self.paged:
            # the paged jit is keyed on the page-rounded length, so the
            # compile counter must be too — exact prompt lengths would
            # overcount
            ps = self.pages.page_size
            n_pg = -(-int(prompts.shape[1]) // ps)
            shape = (len(group), n_pg * ps)
        else:
            shape = (len(group), prompts.shape[1])
        if shape not in self._prefill_shapes_seen:
            self._prefill_shapes_seen.add(shape)
            self._c["prefill_compiles"].inc()
        with obs_trace.span("serve.prefill", batch=len(group),
                            prompt_len=int(prompts.shape[1])):
            if self.paged:
                # prefill at the prompt length rounded up to a page multiple
                # — the group cache then splits exactly into pages, and the
                # per-rounded-length jit keeps compile count page-granular
                logits, grp = self._prefill_fn(n_pg * ps)(self.params, inputs)
                page_rows = np.asarray(
                    [self._slot_pages[s][:n_pg] for s in slots], np.int32)
                self._key, sub = jax.random.split(self._key)
                toks = np.asarray(_pick(logits, self.scfg.temperature, sub))
                self.caches = self._insert_pages(
                    self.caches, grp, jnp.asarray(slots),
                    jnp.asarray(page_rows))
            else:
                logits, grp = self._prefill(self.params, inputs)
                self._key, sub = jax.random.split(self._key)
                toks = np.asarray(_pick(logits, self.scfg.temperature, sub))
                self.caches = self._insert(self.caches, grp,
                                           jnp.asarray(slots))
            jax.block_until_ready(logits)
        dt = time.perf_counter() - t0
        self._c["prefill_s"].inc(dt)
        self._h_prefill.record(dt)
        self._c["prefill_tokens"].inc(int(prompts.size))
        self._c["admitted"].inc(len(group))
        if self.recorder is not None:
            self.recorder.record("prefill", prompt_len=int(prompts.shape[1]),
                                 batch=len(group), dtype=self.cfg.dtype,
                                 occupancy=self.pool.occupancy,
                                 queue_depth=self.pool.queue_depth)
        now = time.perf_counter()
        for (slot, req), tok in zip(group, toks):
            req.admitted_at = now
            self._h_ttft.record(now - req.submitted_at)
            if self.paged:
                # register BEFORE _emit: a 1-token request releases its slot
                # (and pages) inside _emit, and the prefix cache must take
                # its references first
                self._register_prefix(req, slot)
            self.tokens[slot] = int(tok)
            self._emit(slot, req, int(tok), finished)

    # ------------------------------------------------------ paged internals
    def _admissible(self, worst: int) -> bool:
        """Could a ``worst``-page request start right NOW (the 'reject'
        admission policy's test)?  Conservative: prefix-cache hits it might
        get are not counted, reclaimable cache pages are."""
        evictable = self.prefix.evictable_pages if self.prefix else 0
        return (self.pool.free_slots > 0 and self.pool.queue_depth == 0
                and worst <= self.pages.free_pages + evictable)

    def _admit_paged(self, finished: list[Request]) -> None:
        """FIFO admission gated on pages: admit head-of-line requests while
        a slot AND their worst-case pages are available; the first request
        that does not fit blocks the line (no lookahead — smaller requests
        behind it cannot starve it)."""
        groups: dict[Any, list[tuple[int, Request]]] = {}
        while self.pool.free_slots:
            req = self.pool.peek()
            if req is None:
                break
            plan = self._plan_pages(req)
            if plan is None:
                break
            slot, _ = self.pool.admit_one()
            self._install(slot, req, plan, groups)
        for group in groups.values():
            self._admit_group(group, finished)

    def _plan_pages(self, req: Request) -> tuple[list[int], list[int]] | None:
        """Reserve every page ``req`` could ever need — shared prefix pages
        first (one pool ref each via lookup), the rest allocated fresh, so
        decode NEVER allocates and can never deadlock mid-generation.
        Returns ``(shared, fresh)`` or None (caller waits); on failure any
        retained shared pages are released."""
        ps = self.pages.page_size
        worst = -(-(len(req.prompt) + req.max_new_tokens) // ps)
        shared: list[int] = []
        if self.prefix is not None and not (req.extra and "embeds" in req.extra):
            # embedding prompts carry content outside the token ids, which
            # is all the prefix hash sees — never share those
            shared = self.prefix.lookup(req.prompt)
        need = worst - len(shared)
        fresh = self.pages.alloc(need)
        if fresh is None and self.prefix is not None:
            # squeeze idle prefix entries before making the line wait
            self.prefix.evict(need - self.pages.free_pages)
            fresh = self.pages.alloc(need)
        if fresh is None:
            if shared:
                self.pages.release(shared)
            return None
        return shared, fresh

    def _install(self, slot: int, req: Request,
                 plan: tuple[list[int], list[int]],
                 groups: dict[Any, list[tuple[int, Request]]]) -> None:
        """Wire an admitted request's page table and route it to a prefill
        path: chunked (prefix hit — only the tail needs compute — or prompt
        longer than ``prefill_chunk``) or the same-shape batched group."""
        shared, fresh = plan
        ps = self.pages.page_size
        pages = shared + fresh
        self._slot_pages[slot] = pages
        self._pt[slot] = 0
        self._pt[slot, :len(pages)] = pages
        m_tok = len(shared) * ps
        cs = self.scfg.prefill_chunk
        if m_tok or (cs is not None and len(req.prompt) - m_tok > cs):
            if m_tok:
                self._c["prefix_hits"].inc()
                self._c["prefix_tokens_saved"].inc(m_tok)
            # the slot's cache position starts at the shared-prefix length
            # (0 when none) — eviction is lazy, so the leaf holds the
            # previous occupant's value until set here
            self.caches = self._set_len(self.caches, jnp.int32(slot),
                                        jnp.int32(m_tok))
            self._prefilling.add(slot)
            self._chunk_tasks.append(_ChunkTask(req=req, slot=slot,
                                                pos=m_tok))
        else:
            groups.setdefault(_shape_key(req), []).append((slot, req))

    def _prefill_fn(self, r: int):
        fn = self._prefill_by_len.get(r)
        if fn is None:
            fn = self._build_prefill(r)
            self._prefill_by_len[r] = fn
        return fn

    def _chunk_step(self, finished: list[Request]) -> None:
        """Advance the head chunk task by ONE chunk — chunked prefill
        interleaves with decode at chunk granularity, so a long prompt
        cannot stall the decode batch for its whole length.  The final
        (short) chunk runs zero-padded at the fixed chunk shape with a
        traced valid-length, so compiles scale with chunk SHAPES, not
        prompt lengths."""
        self._maybe_refresh_schedules()
        task = self._chunk_tasks[0]
        req, slot = task.req, task.slot
        remaining = len(req.prompt) - task.pos
        cs = self.scfg.prefill_chunk or remaining
        n = min(cs, remaining)
        buf = np.zeros((1, cs), np.int32)
        buf[0, :n] = req.prompt[task.pos:task.pos + n]
        embeds = None
        eshape = None
        if req.extra and "embeds" in req.extra:
            e = np.asarray(req.extra["embeds"])
            ebuf = np.zeros((1, cs) + e.shape[1:], e.dtype)
            ebuf[0, :n] = e[task.pos:task.pos + n]
            embeds = jnp.asarray(ebuf)
            eshape = tuple(e.shape[1:])
        shape = ("chunk", cs, eshape)
        if shape not in self._prefill_shapes_seen:
            self._prefill_shapes_seen.add(shape)
            self._c["prefill_compiles"].inc()
        t0 = time.perf_counter()
        with obs_trace.span("serve.prefill_chunk", slot=slot, chunk=int(cs),
                            valid=int(n)):
            last, self.caches = self._chunk(
                self.params, self.caches, jnp.asarray(buf),
                jnp.asarray(self._pt[slot:slot + 1]), jnp.int32(slot),
                jnp.int32(n), embeds=embeds)
            jax.block_until_ready(last)
        dt = time.perf_counter() - t0
        self._c["prefill_s"].inc(dt)
        self._h_prefill.record(dt)
        self._c["prefill_tokens"].inc(int(n))
        self._c["chunk_steps"].inc()
        if self.recorder is not None:
            self.recorder.record("prefill", prompt_len=int(cs), batch=1,
                                 dtype=self.cfg.dtype,
                                 occupancy=self.pool.occupancy,
                                 queue_depth=self.pool.queue_depth)
        task.pos += n
        if task.pos < len(req.prompt):
            return
        self._chunk_tasks.popleft()
        self._prefilling.discard(slot)
        self._key, sub = jax.random.split(self._key)
        tok = int(np.asarray(_pick(last, self.scfg.temperature, sub))[0])
        now = time.perf_counter()
        req.admitted_at = now
        self._h_ttft.record(now - req.submitted_at)
        self._c["admitted"].inc()
        self._register_prefix(req, slot)
        self.tokens[slot] = tok
        self._emit(slot, req, tok, finished)

    def _register_prefix(self, req: Request, slot: int) -> None:
        """Offer a freshly prefilled prompt's full pages to the prefix cache
        (idempotent for already-known blocks)."""
        if self.prefix is None or (req.extra and "embeds" in req.extra):
            return
        n_full = (len(req.prompt) - 1) // self.pages.page_size
        if n_full:
            # the FULL prompt goes to insert — its key chain already stops
            # at the last shareable block; truncating first would shift that
            # bound and silently drop the final block
            self.prefix.insert(req.prompt, self._slot_pages[slot][:n_full])

    def _decode_paged(self, finished: list[Request]) -> None:
        """One lockstep decode over slots NOT mid chunked-prefill: the
        ``active`` mask keeps inactive rows from writing real pages or
        advancing their cache position."""
        decoding = [s for s, _ in self.pool.held()
                    if s not in self._prefilling]
        if not decoding:
            return
        self._maybe_refresh_schedules()
        occ = len(decoding)
        active = np.zeros(self.capacity, bool)
        active[decoding] = True
        t0 = time.perf_counter()
        with obs_trace.span("serve.decode", occupancy=occ):
            self._key, sub = jax.random.split(self._key)
            tok, self.caches = self._decode(
                self.params, self.caches, jnp.asarray(self.tokens),
                jnp.asarray(self._pt), jnp.asarray(active), key=sub)
            tok = np.asarray(tok)
        dt = time.perf_counter() - t0
        self._c["decode_s"].inc(dt)
        self._c["decode_steps"].inc()
        self._h_decode.record(dt)
        if self.recorder is not None:
            self.recorder.record("decode", batch=self.capacity,
                                 dtype=self.cfg.dtype, occupancy=occ,
                                 queue_depth=self.pool.queue_depth)
        for slot, req in list(self.pool.held()):
            if slot in self._prefilling:
                continue
            self.tokens[slot] = int(tok[slot])
            self._emit(slot, req, int(tok[slot]), finished)

    def _emit(self, slot: int, req: Request, tok: int,
              finished: list[Request]) -> None:
        req.tokens.append(tok)
        now = time.perf_counter()
        last = self._last_emit.get(req.uid)
        if last is not None:
            self._h_itl.record(now - last)
        self._last_emit[req.uid] = now
        self._c["tokens_out"].inc()
        if self.on_token is not None:
            self.on_token(req, tok)
        if (len(req.tokens) >= req.max_new_tokens
                or (req.eos_id is not None and tok == req.eos_id)):
            req.finished_at = time.perf_counter()
            self._last_emit.pop(req.uid, None)
            # eviction is lazy: a freed slot's stale state is confined to its
            # own batch row (per-slot masks/state), and the next admission's
            # insert overwrites the entire row — so completion costs no
            # cache-sized dispatch (models.evict_slot exists for callers that
            # want eager invalidation)
            self.pool.release(slot)
            if self.paged:
                # drop the slot's page references (prefix-shared pages stay
                # alive through the cache's own ref) and zero its page-table
                # row so stale decode scatters land in the trash page
                pages = self._slot_pages.pop(slot, None)
                if pages:
                    self.pages.release(pages)
                self._pt[slot] = 0
            self._c["completed"].inc()
            finished.append(req)

    # -------------------------------------------------------------- metrics
    @property
    def stats(self) -> dict[str, Any]:
        """Cumulative counters, assembled from the metrics registry (the
        registry instruments are the source of truth; this dict keeps the
        pre-registry read surface)."""
        return {k: c.value for k, c in self._c.items()}

    def reset_stats(self) -> None:
        """Zero the timing/gauge counters and latency histograms (e.g. after
        a warmup pass) while keeping compile bookkeeping, so metrics
        describe steady state."""
        keep = self._c["prefill_compiles"].value
        for c in self._c.values():
            c.reset()
        if keep:
            self._c["prefill_compiles"].inc(keep)
        for h in (self._h_ttft, self._h_itl, self._h_prefill, self._h_decode):
            h.reset()

    def metrics(self) -> dict[str, float]:
        """Derived serving metrics (gauge means are per engine step).

        Every ratio goes through :func:`_ratio`, so a never-stepped or
        zero-token engine reports well-defined 0.0 everywhere instead of
        raising or emitting inf/NaN."""
        s = self.stats
        busy = s["prefill_s"] + s["decode_s"]
        out = {
            "queue_depth": float(self.pool.queue_depth),
            "slot_occupancy": float(self.pool.occupancy),
            "mean_occupancy": _ratio(s["occupancy_sum"], s["steps"]),
            "mean_queue_depth": _ratio(s["queue_depth_sum"], s["steps"]),
            "prefill_s": float(s["prefill_s"]),
            "decode_s": float(s["decode_s"]),
            "prefill_frac": _ratio(s["prefill_s"], busy),
            "tokens_per_s": _ratio(s["tokens_out"], busy),
            "decode_tokens_per_s": _ratio(s["tokens_out"] - s["admitted"],
                                          s["decode_s"]),
        }
        if self.paged:
            out.update({
                "page_occupancy": _ratio(self.pages.used_pages,
                                         self.pages.usable_pages),
                "free_pages": float(self.pages.free_pages),
                "prefix_hits": float(s["prefix_hits"]),
                "prefix_tokens_saved": float(s["prefix_tokens_saved"]),
                "prefix_entries": float(len(self.prefix)
                                        if self.prefix else 0),
                "chunk_steps": float(s["chunk_steps"]),
            })
        return out


def _decode_sample(params, caches, token, *, cfg: ModelConfig,
                   temperature: float, key):
    logits, caches = M.decode_step(params, caches, token, cfg)
    return _pick(logits, temperature, key), caches


def _decode_sample_paged(params, caches, token, pt, active, *,
                         cfg: ModelConfig, temperature: float, key):
    logits, caches = M.decode_step(params, caches, token, cfg, pt=pt,
                                   active=active)
    return _pick(logits, temperature, key), caches


def _pick(logits, temperature: float, key):
    if temperature and temperature > 0:
        return jax.random.categorical(key, logits / temperature, axis=-1)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)
