"""Paged-KV serving: differential correctness vs the static reference
engine (greedy token identity across arrival orderings, with prefix sharing
and chunked prefill on), page accounting (no leaks, reservation-at-admission),
the relaxed page-capacity admission bound, the compile-count guarantee of
fixed chunk shapes, and the step's spans on the profiler's timeline and the
tracer with the request timestamps and counters read from them."""

import dataclasses
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models import model as M
from repro.models import modules as nn
from repro.models.config import ModelConfig
from repro.serve.engine import ContinuousEngine, Engine, ServeConfig
from repro.serve.pages import PagesExhausted

CFG = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                  dtype="float32").validate()
MAX_LEN = 48


@pytest.fixture(scope="module")
def params():
    return nn.unwrap(M.init_lm(jax.random.PRNGKey(0), CFG))


def _requests(rng, n, lo=3, hi=28, new=(2, 8)):
    reqs = [(rng.integers(1, CFG.vocab, int(rng.integers(lo, hi)))
             .astype(np.int32), int(rng.integers(*new))) for _ in range(n)]
    # force one shared >1-page prefix pair into every mix
    p, b = reqs[0]
    reqs.append((np.concatenate([p[:len(p) - 1], [7, 9, 11]])
                 .astype(np.int32), b))
    return reqs


@pytest.fixture(scope="module")
def reference(params):
    rng = np.random.default_rng(0)
    reqs = _requests(rng, 5)
    ref = Engine(params, CFG, ServeConfig(max_len=MAX_LEN))
    outs = [ref.generate(p[None], b)[0] for p, b in reqs]
    return reqs, outs


def _paged_cfg(**kw):
    base = dict(max_len=MAX_LEN, capacity=3, paged=True, page_size=8,
                prefill_chunk=8)
    base.update(kw)
    return ServeConfig(**base)


class TestDifferential:
    """Greedy paged output == static Engine output, token for token."""

    @pytest.mark.parametrize("order", ["fifo", "reversed", "staggered"])
    def test_arrival_orderings(self, params, reference, order):
        reqs, outs = reference
        eng = ContinuousEngine(params, CFG, _paged_cfg())
        idxs = list(range(len(reqs)))
        if order == "reversed":
            idxs = idxs[::-1]
        uid_to_idx = {}
        if order == "staggered":
            # half up front, the rest arriving mid-flight
            for i in idxs[:2]:
                uid_to_idx[eng.submit(*reqs[i]).uid] = i
            got = {}
            for _ in range(3):
                for r in eng.step():
                    got[r.uid] = r.output
            for i in idxs[2:]:
                uid_to_idx[eng.submit(*reqs[i]).uid] = i
            got.update(eng.run(max_steps=500))
        else:
            for i in idxs:
                uid_to_idx[eng.submit(*reqs[i]).uid] = i
            got = eng.run(max_steps=500)
        for uid, i in uid_to_idx.items():
            assert np.array_equal(got[uid], outs[i]), \
                f"req {i} diverged under {order} arrival"
        # all pages back except the prefix cache's own references
        assert eng.pages.used_pages == (len(eng.prefix)
                                        if eng.prefix else 0)

    def test_no_prefix_no_chunk_matches_too(self, params, reference):
        reqs, outs = reference
        eng = ContinuousEngine(params, CFG, _paged_cfg(
            prefix_cache=False, prefill_chunk=None))
        uids = [eng.submit(p, b).uid for p, b in reqs]
        got = eng.run(max_steps=500)
        for uid, out in zip(uids, outs):
            assert np.array_equal(got[uid], out)
        assert eng.pages.used_pages == 0          # nothing may leak

    def test_tight_pool_queues_and_completes(self, params, reference):
        """Fewer pages than the workload's worst case: admission must make
        the head of line wait (never deadlock, never corrupt) and still
        reproduce the reference stream."""
        reqs, outs = reference
        eng = ContinuousEngine(params, CFG, _paged_cfg(
            capacity=2, num_pages=11))
        uids = [eng.submit(p, b).uid for p, b in reqs]
        got = eng.run(max_steps=1000)
        for uid, out in zip(uids, outs):
            assert np.array_equal(got[uid], out)


class TestPrefixSharing:
    def test_sequential_identical_prefixes_hit(self, params):
        rng = np.random.default_rng(3)
        base = rng.integers(1, CFG.vocab, 21).astype(np.int32)
        tail = np.concatenate([base[:20],
                               rng.integers(1, CFG.vocab, 6)]).astype(np.int32)
        ref = Engine(params, CFG, ServeConfig(max_len=MAX_LEN))
        want = [ref.generate(p[None], 5)[0] for p in (base, tail)]

        eng = ContinuousEngine(params, CFG, _paged_cfg(capacity=2))
        r1 = eng.submit(base, 5)
        out = eng.run(max_steps=200)
        assert np.array_equal(out[r1.uid], want[0])
        r2 = eng.submit(tail, 5)
        out = eng.run(max_steps=200)
        assert np.array_equal(out[r2.uid], want[1])
        # 20 shared tokens / 8-token pages -> 2 full pages skipped
        assert eng.stats["prefix_hits"] == 1
        assert eng.stats["prefix_tokens_saved"] == 16
        assert eng.metrics()["prefix_hits"] == 1.0

    def test_shared_pages_survive_owner_eviction(self, params):
        """The cache's reference keeps a registered page alive after the
        registering request finishes — a later identical prompt still hits."""
        rng = np.random.default_rng(4)
        p = rng.integers(1, CFG.vocab, 17).astype(np.int32)
        eng = ContinuousEngine(params, CFG, _paged_cfg(capacity=1))
        eng.submit(p, 3)
        eng.run(max_steps=200)
        assert len(eng.prefix) == 2               # (17-1)//8 blocks
        held = eng.pages.used_pages
        assert held == 2                          # only cache refs remain
        eng.submit(p, 3)
        eng.run(max_steps=200)
        assert eng.stats["prefix_hits"] == 1


class TestChunkedPrefill:
    def test_compiles_bounded_by_chunk_shapes(self, params):
        """Prompt LENGTHS must not drive prefill compiles: every chunked
        prompt reuses the one (1, chunk) trace, padded tail included."""
        rng = np.random.default_rng(5)
        eng = ContinuousEngine(params, CFG, _paged_cfg(
            prefix_cache=False, capacity=2))
        lens = [12, 17, 23, 27, 40]               # all > chunk, all distinct
        refs = []
        for n in lens:
            p = rng.integers(1, CFG.vocab, n).astype(np.int32)
            refs.append((eng.submit(p, 3), p))
        eng.run(max_steps=500)
        assert eng.stats["chunk_steps"] == sum(-(-n // 8) for n in lens)
        assert eng.stats["prefill_compiles"] == 1
        # and decode emitted everything it owed
        assert eng.stats["completed"] == len(lens)

    def test_group_prefill_compiles_counted_page_granular(self, params):
        """Un-chunked paged prefills jit at the page-rounded length, so the
        compile counter must key on that too: distinct exact prompt lengths
        rounding to the same page count are ONE compile, not one each."""
        eng = ContinuousEngine(params, CFG, _paged_cfg(
            prefix_cache=False, prefill_chunk=None, capacity=1))
        rng = np.random.default_rng(7)
        for n in (3, 5, 7):               # all round up to one 8-token page
            eng.submit(rng.integers(1, CFG.vocab, n).astype(np.int32), 2)
            eng.run(max_steps=100)
        assert eng.stats["prefill_compiles"] == 1

    def test_padded_final_chunk_overflowing_full_table(self, params):
        """A final zero-padded chunk can overrun the slot's page table when
        the worst-case reservation fills it entirely; those positions must
        scatter to the trash page, not wrap onto the table's LAST real page
        and overwrite live prompt/decode KV (regression: the overflow was
        clamped to the last page-table column)."""
        # worst = ceil((45+3)/8) = 6 pages == the full ceil(48/8) table, and
        # the final chunk covers positions [40, 60) — 48..59 overflow
        scfg = ServeConfig(max_len=48, capacity=1, paged=True, page_size=8,
                           prefill_chunk=20, prefix_cache=False)
        eng = ContinuousEngine(params, CFG, scfg)
        prompt = np.arange(1, 46, dtype=np.int32)
        r = eng.submit(prompt, 3)
        out = eng.run(max_steps=200)
        ref = Engine(params, CFG, ServeConfig(max_len=48))
        assert np.array_equal(out[r.uid], ref.generate(prompt[None], 3)[0])

    def test_long_prompt_interleaves_with_decode(self, params):
        """A long chunked prompt must not stall an in-flight decode: the
        short request keeps emitting tokens while the long one prefills."""
        rng = np.random.default_rng(6)
        short = rng.integers(1, CFG.vocab, 4).astype(np.int32)
        long = rng.integers(1, CFG.vocab, 40).astype(np.int32)
        eng = ContinuousEngine(params, CFG, _paged_cfg(
            capacity=2, prefix_cache=False, prefill_chunk=8))
        rs = eng.submit(short, 8)
        rl = eng.submit(long, 3)
        steps = 0
        while not rl.tokens:                       # long still chunking
            eng.step()
            steps += 1
            assert steps < 50
        # 40 tokens / 8-token chunks = 5 prefill steps, and the short
        # request emitted a token through every one of them
        assert len(rs.tokens) >= 4
        eng.run(max_steps=200)
        ref = Engine(params, CFG, ServeConfig(max_len=MAX_LEN))
        assert np.array_equal(rl.output, ref.generate(long[None], 3)[0])
        assert np.array_equal(rs.output, ref.generate(short[None], 8)[0])


class TestAdmissionBounds:
    def test_dense_engine_still_rejects_past_max_len(self, params):
        eng = ContinuousEngine(params, CFG, ServeConfig(max_len=MAX_LEN))
        eng.submit(np.arange(1, 41, dtype=np.int32), 8)     # == max_len: ok
        with pytest.raises(ValueError, match="max_len"):
            eng.submit(np.arange(1, 41, dtype=np.int32), 9)  # one past: no

    def test_paged_accepts_up_to_page_rounded_bound(self, params):
        """The old hard ``> max_len`` rejection is gone in paged mode: the
        bound is the page table (max_len rounded UP to pages), and a request
        in the formerly rejected gap completes correctly."""
        scfg = ServeConfig(max_len=40, capacity=2, paged=True, page_size=16,
                           prefill_chunk=8, prefix_cache=False)
        eng = ContinuousEngine(params, CFG, scfg)
        prompt = np.arange(1, 41, dtype=np.int32)            # 40 + 4 > max_len
        r = eng.submit(prompt, 4)                            # but <= 3*16
        out = eng.run(max_steps=300)
        ref = Engine(params, CFG, ServeConfig(max_len=48))
        assert np.array_equal(out[r.uid], ref.generate(prompt[None], 4)[0])
        with pytest.raises(ValueError, match="page table"):
            eng.submit(prompt, 9)                            # 49 > 48: never

    def test_never_fits_raises_even_with_queue_policy(self, params):
        eng = ContinuousEngine(params, CFG, ServeConfig(
            max_len=MAX_LEN, capacity=2, paged=True, page_size=8,
            num_pages=4))                                    # 3 usable pages
        with pytest.raises(ValueError, match="never"):
            eng.submit(np.arange(1, 30, dtype=np.int32), 4)  # needs 5 pages

    def test_reject_policy_raises_when_it_cannot_start_now(self, params):
        eng = ContinuousEngine(params, CFG, ServeConfig(
            max_len=MAX_LEN, capacity=1, paged=True, page_size=8,
            admission="reject", prefix_cache=False))
        p = np.arange(1, 10, dtype=np.int32)
        r1 = eng.submit(p, 3)                    # queue empty: accepted
        with pytest.raises(PagesExhausted):
            eng.submit(p, 3)                     # r1 is ahead of it
        eng.run(max_steps=200)
        assert r1.done
        r2 = eng.submit(p, 3)                    # capacity is back: accepted
        eng.run(max_steps=200)
        assert r2.done

    def test_queue_policy_waits_instead(self, params):
        eng = ContinuousEngine(params, CFG, ServeConfig(
            max_len=MAX_LEN, capacity=1, paged=True, page_size=8,
            prefix_cache=False))
        p = np.arange(1, 10, dtype=np.int32)
        rs = [eng.submit(p, 3) for _ in range(3)]
        eng.run(max_steps=500)
        assert all(r.done for r in rs)


class TestGating:
    def test_paged_rejects_non_attention_families(self):
        ssm = ModelConfig(name="s", family="ssm", n_layers=2, d_model=64,
                          n_heads=0, n_kv_heads=0, d_ff=0, vocab=64,
                          ssm_state=16, ssm_headdim=32, ssm_chunk=8,
                          dtype="float32").validate()
        params = nn.unwrap(M.init_lm(jax.random.PRNGKey(0), ssm))
        with pytest.raises(ValueError, match="paged"):
            ContinuousEngine(params, ssm, _paged_cfg())

    def test_bad_admission_policy_rejected(self, params):
        with pytest.raises(ValueError, match="admission"):
            ContinuousEngine(params, CFG, _paged_cfg(admission="drop"))

    def test_paged_decode_step_guard(self, params):
        ssm = ModelConfig(name="s", family="ssm", n_layers=2, d_model=64,
                          n_heads=0, n_kv_heads=0, d_ff=0, vocab=64,
                          ssm_state=16, ssm_headdim=32, ssm_chunk=8,
                          dtype="float32").validate()
        sp = nn.unwrap(M.init_lm(jax.random.PRNGKey(0), ssm))
        eng = ContinuousEngine(sp, ssm, ServeConfig(max_len=16))
        with pytest.raises(ValueError, match="attention"):
            M.decode_step(sp, eng.caches, np.zeros(8, np.int32), ssm,
                          pt=np.zeros((8, 2), np.int32))


# ============================================= the layer loop's flat store
# Three layers, three slots of four 8-token pages over a 13-page pool (page 0
# is each layer's trash page): the paged decode and chunk steps against the
# dense slot-cache decode on the same cache contents.
CFG3 = ModelConfig(name="t3", family="dense", n_layers=3, d_model=64,
                   n_heads=4, n_kv_heads=2, d_ff=128, vocab=128,
                   dtype="float32").validate()
CAP, PS, N_PG, POOL = 3, 8, 4, 13
LENS = np.array([5, 0, 17], np.int32)


@pytest.fixture(scope="module")
def params3():
    return nn.unwrap(M.init_lm(jax.random.PRNGKey(1), CFG3))


def _store_and_dense(params3, cfg):
    """A paged cache filled with noise, its page tables, and the dense
    (L, B, N_PG*PS, H, D) slot cache that holds the same positions."""
    ex = {"tokens": np.zeros((1, 8), np.int32)}
    caches, axes = M.alloc_paged_caches(params3, cfg, CAP, N_PG * PS, PS,
                                        POOL, ex)
    rng = np.random.default_rng(3)
    for n in ("k", "v"):
        caches[n] = jnp.asarray(
            rng.standard_normal(caches[n].shape).astype(np.float32))
    caches["len"] = jnp.asarray(np.tile(LENS, (cfg.n_layers, 1)))
    pt = rng.permutation(np.arange(1, POOL))[:CAP * N_PG].reshape(CAP, N_PG)
    pt = pt.astype(np.int32)
    l, _, ps, h, d = caches["k"].shape
    dense = {n: np.asarray(caches[n])[:, pt].reshape(l, CAP, N_PG * ps, h, d)
             for n in ("k", "v")}
    return caches, axes, pt, dense


def _pages_of(dense, pt, rows):
    """Expected page contents (L, POOL, ps, H, D) after writing the dense
    caches' rows ``rows`` back through their page tables; every other page
    keeps ``dense["init"]``."""
    out = np.array(dense["init"])
    l, pool, ps, h, d = out.shape
    for b in rows:
        out[:, pt[b]] = dense["new"][:, b].reshape(l, N_PG, ps, h, d)
    return out


def _assert_layers_equal(got, want):
    """Layer by layer, every page but that layer's trash page (index 0 of
    the layer: page ``l*POOL`` of the flat store)."""
    for layer in range(got.shape[0]):
        np.testing.assert_array_equal(got[layer, 1:], want[layer, 1:],
                                      err_msg=f"layer {layer}")


@pytest.mark.parametrize("use_pallas", [False, True])
@pytest.mark.parametrize("scan_layers", [True, False])
class TestFlatStoreLayerLoop:
    """The paged layer loop carries the page store flat and addresses each
    layer's pages at ``layer * P + page``: bit-identical to the dense
    slot-cache decode, and no layer's writes land in another layer's pages."""

    def test_decode_step_matches_dense(self, params3, scan_layers,
                                       use_pallas):
        cfg = dataclasses.replace(CFG3, scan_layers=scan_layers,
                                  use_pallas=use_pallas)
        caches, _, pt, dense = _store_and_dense(params3, cfg)
        tok = jnp.asarray([3, 4, 5], jnp.int32)
        active = np.array([True, False, True])
        init = {n: np.asarray(caches[n]) for n in ("k", "v")}
        lens = np.asarray(caches["len"])

        d_logits, d_new = jax.jit(functools.partial(M.decode_step, cfg=cfg))(
            params3, {"k": jnp.asarray(dense["k"]),
                      "v": jnp.asarray(dense["v"]),
                      "len": jnp.asarray(lens)}, tok)
        logits, new = jax.jit(functools.partial(M.decode_step, cfg=cfg),
                              donate_argnums=(1,))(
            params3, caches, tok, pt=jnp.asarray(pt),
            active=jnp.asarray(active))

        rows = np.flatnonzero(active)
        np.testing.assert_array_equal(np.asarray(logits)[rows],
                                      np.asarray(d_logits)[rows])
        for n in ("k", "v"):
            want = _pages_of({"init": init[n], "new": np.asarray(d_new[n])},
                             pt, rows)
            got = np.asarray(new[n])
            _assert_layers_equal(got, want)
            # the idle row (len 0) wrote its token into offset 0 of every
            # layer's own trash page
            for layer in range(cfg.n_layers):
                assert not np.array_equal(got[layer, 0, 0],
                                          init[n][layer, 0, 0]), layer
        np.testing.assert_array_equal(np.asarray(new["len"]),
                                      lens + active)

    def test_padded_final_chunk_matches_dense(self, params3, scan_layers,
                                              use_pallas):
        cfg = dataclasses.replace(CFG3, scan_layers=scan_layers,
                                  use_pallas=use_pallas)
        caches, axes, pt, dense = _store_and_dense(params3, cfg)
        slot, n_valid = 0, 5               # 8-token chunk, 5 real tokens
        chunk = np.random.default_rng(4).integers(1, CFG3.vocab, (1, PS))
        chunk[:, n_valid:] = 0
        chunk = jnp.asarray(chunk.astype(np.int32))
        init = {n: np.asarray(caches[n]) for n in ("k", "v")}
        lens = np.asarray(caches["len"])

        one = {n: jnp.asarray(dense[n][:, slot:slot + 1]) for n in ("k", "v")}
        one["len"] = jnp.asarray(lens[:, slot])     # (L,) scalar per layer
        d_logits, d_new, _ = jax.jit(
            functools.partial(M.decode_tokens, cfg=cfg))(params3, one, chunk)
        last, new, _ = jax.jit(functools.partial(M.prefill_chunk, cfg=cfg,
                                              axes=axes),
                            donate_argnums=(1,))(
            params3, caches, chunk, jnp.asarray(pt[slot:slot + 1]), slot,
            n_valid)

        np.testing.assert_array_equal(np.asarray(last),
                                      np.asarray(d_logits)[:, n_valid - 1])
        for n in ("k", "v"):
            new_rows = np.array(dense[n])
            new_rows[:, slot] = np.asarray(d_new[n])[:, 0]
            want = _pages_of({"init": init[n], "new": new_rows}, pt, [slot])
            _assert_layers_equal(np.asarray(new[n]), want)
        want_len = lens.copy()
        want_len[:, slot] += n_valid
        np.testing.assert_array_equal(np.asarray(new["len"]), want_len)


def test_served_gather_keys_the_adapters_schedule(params3, monkeypatch):
    """The autotune adapter tunes ``paged_gather`` on one layer's pool; the
    layer loop gathers from the flat store of every layer.  Both resolve
    the same schedule key."""
    from repro.autotune.adapters import serve_targets
    from repro.kernels.paged_attention import ops as pg_ops
    from repro.obs.recorder import WorkloadKey

    cfg = dataclasses.replace(CFG3, use_pallas=True)
    kern, real, served = pg_ops.kernel(), pg_ops.paged_gather, []

    def gather(store, table):
        served.append(kern.static_of(store, table))
        return real(store, table)

    monkeypatch.setattr(pg_ops, "paged_gather", gather)
    caches, _, pt, _ = _store_and_dense(params3, cfg)
    M.decode_step(params3, caches, jnp.zeros((CAP,), jnp.int32), cfg,
                  pt=jnp.asarray(pt), active=jnp.ones((CAP,), bool))
    scfg = ServeConfig(max_len=N_PG * PS, capacity=CAP, paged=True,
                       page_size=PS, num_pages=POOL)
    target = serve_targets(cfg, scfg)(WorkloadKey("decode", 0, CAP,
                                                  "float32"))
    tuned = kern.static_of(*target.workload.make_args(
        np.random.default_rng(0)))
    assert served and all(s == tuned for s in served), (served, tuned)


#: an HLO instruction's result dims and opcode: `%x = f32[3,13]{1,0} copy(`
_HLO_OP = re.compile(r"= \w+\[([\d,]*)\]\{[\d,]*\} ([\w-]+)\(")


@pytest.mark.parametrize("scan_layers", [True, False])
@pytest.mark.parametrize("program", ["decode", "chunk"])
def test_paged_programs_do_not_restack_the_store(params3, program,
                                                 scan_layers):
    """The compiled paged decode and chunk programs (cache donated, as the
    engine dispatches them) hold no op that rebuilds or copies a whole page
    store: no dynamic-update-slice, concatenate or copy whose result has
    the store's (L, P, ps, H, D) shape or its flat (L*P, ps, H, D) form."""
    cfg = dataclasses.replace(CFG3, scan_layers=scan_layers)
    caches, axes, pt, _ = _store_and_dense(params3, cfg)
    if program == "decode":
        def fn(p, c, t, pt_, a):
            return M.decode_step(p, c, t, cfg, pt=pt_, active=a)
        args = (params3, caches, jnp.zeros((CAP,), jnp.int32),
                jnp.asarray(pt), jnp.ones((CAP,), bool))
    else:
        fn = functools.partial(M.prefill_chunk, cfg=cfg, axes=axes)
        args = (params3, caches, jnp.zeros((1, PS), jnp.int32),
                jnp.asarray(pt[:1]), 0, 5)
    hlo = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile().as_text()
    l, pool, ps, h, d = caches["k"].shape
    stores = {f"{l},{pool},{ps},{h},{d}", f"{l * pool},{ps},{h},{d}"}
    bad = [m.group(0) for m in _HLO_OP.finditer(hlo)
           if m.group(1) in stores
           and m.group(2) in ("dynamic-update-slice", "concatenate", "copy")]
    assert not bad, "\n".join(bad)


# ================================================================ tracing
def _parents(spans):
    """name -> set of names of the innermost span holding it, for
    ``(start, end, name)`` spans of one thread."""
    out: dict[str, set[str]] = {}
    for s, e, n in spans:
        holders = [h for h in spans if h[0] <= s and e <= h[1]
                   and (h[1] - h[0]) > (e - s)]
        inner = min(holders, key=lambda h: h[1] - h[0], default=None)
        out.setdefault(n, set()).add(None if inner is None else inner[2])
    return out


#: one level of children under serve.step, one .wait under each dispatch
STEP_TREE = {"serve.step": {None}, "serve.admit": {"serve.step"},
             "serve.prefill": {"serve.step"},
             "serve.prefill.wait": {"serve.prefill"},
             "serve.prefill_chunk": {"serve.step"},
             "serve.prefill_chunk.wait": {"serve.prefill_chunk"},
             "serve.decode": {"serve.step"},
             "serve.decode.wait": {"serve.decode"},
             "serve.emit": {"serve.step"}}


@pytest.fixture(scope="module")
def traced(params, tmp_path_factory):
    """A paged engine with chunked prefill and the prefix cache, run under
    a profiler session and an in-memory tracer at once."""
    import glob
    from jax.profiler import ProfileData
    from repro.obs.trace import Tracer, tracing
    rng = np.random.default_rng(3)
    long = rng.integers(1, CFG.vocab, 20).astype(np.int32)
    reqs = [(long, 4), (rng.integers(1, CFG.vocab, 6).astype(np.int32), 3),
            (rng.integers(1, CFG.vocab, 7).astype(np.int32), 5),
            (np.concatenate([long[:17], [5, 6]]).astype(np.int32), 3)]
    eng = ContinuousEngine(params, CFG, _paged_cfg())
    tr = Tracer()
    out = str(tmp_path_factory.mktemp("profile"))
    jax.profiler.start_trace(out)
    try:
        with tracing(tr):
            handles = [eng.submit(p, b) for p, b in reqs[:3]]
            eng.step()
            handles.append(eng.submit(*reqs[3]))
            eng.run(max_steps=500)
    finally:
        jax.profiler.stop_trace()
    xplane, = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
    xspans = [(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
              for plane in ProfileData.from_file(xplane).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name.startswith("serve.")]
    return eng, handles, tr.events(), xspans


class TestTracing:
    """Spans on the profiler's timeline and the tracer, and the request
    timestamps and counters behind the scheduler's per-layer metrics."""

    def test_profiler_timeline_holds_the_step_tree(self, traced):
        _, _, _, xspans = traced
        assert _parents(xspans) == STEP_TREE

    def test_tracer_holds_the_same_tree_and_validates(self, traced):
        from repro.obs.trace import validate_events
        _, _, events, _ = traced
        assert validate_events(events) == []
        spans = [(e["ts"], e["ts"] + e["dur"], e["name"]) for e in events
                 if e["ph"] == "X"]
        assert _parents(spans) == STEP_TREE

    def test_prefill_wait_is_the_sum_over_requests(self, traced):
        eng, handles, _, _ = traced
        s = eng.stats
        assert s["prefill_starts"] == len(handles)
        assert s["prefill_wait_s"] == pytest.approx(
            sum(h.prefill_at - h.submitted_at for h in handles), rel=1e-9)
        for h in handles:
            assert (h.submitted_at <= h.slot_at <= h.prefill_at
                    <= h.first_token_at <= h.finished_at)

    def test_host_time_is_inside_the_steps(self, traced):
        eng, _, events, _ = traced
        steps = [e["dur"] * 1e-6 for e in events
                 if e["name"] == "serve.step"]
        waits = [e["dur"] * 1e-6 for e in events
                 if e["name"].endswith(".wait")]
        host = eng.stats["host_s"]
        assert len(steps) == eng.stats["steps"]
        assert 0 < host <= sum(steps)
        assert host == pytest.approx(sum(steps) - sum(waits), abs=1e-3)

    def test_finished_requests_are_instants(self, traced):
        _, handles, events, _ = traced
        got = {e["args"]["uid"]: e["args"] for e in events
               if e["name"] == "serve.request"}
        assert sorted(got) == sorted(h.uid for h in handles)
        for h in handles:
            a = got[h.uid]
            assert a["tokens"] == len(h.tokens)
            assert a["ttft_s"] == pytest.approx(
                h.first_token_at - h.submitted_at)
            assert a["slot_wait_s"] <= a["prefill_wait_s"] <= a["ttft_s"]
