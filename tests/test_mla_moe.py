"""DeepSeek-V2's mechanisms against the plain float32 reference
(``bench/reference/deepseek_v2.py``) at a small size: multi-head latent
attention served through the paged latent cache (chunked prefill, a prefix
hit, absorbed decode), the absorbed form against the expanded one, YaRN's
frequencies and softmax scale in closed form, and the expert share: the
shares of one layer add up to the uncut layer."""

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.reference import deepseek_v2 as R
from bench import weights as W
from repro import configs
from repro.models import attention as A
from repro.models import model as M
from repro.models import moe as moe_mod
from repro.models import modules as nn
from repro.serve.engine import ContinuousEngine, ServeConfig

SEED = 2_718_281_828           # above 2**31, as seeds may be

#: a small DeepSeek-V2: every mechanism, with the published YaRN settings
SMALL = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 4, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "intermediate_size": 96,
    "moe_intermediate_size": 32, "routed_experts_published": 8,
    "n_routed_experts": 4, "first_held_expert": 2,
    "num_experts_per_tok": 3, "n_shared_experts": 2, "norm_topk_prob": False,
    "vocab_size": 128, "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "q_lora_rank": None, "hidden_act": "silu", "scoring_func": "softmax",
    "topk_method": "greedy", "routed_scaling_factor": 1, "n_group": 1,
    "topk_group": 1, "moe_layer_freq": 1, "attention_bias": False,
    "tie_word_embeddings": False,
}
PS, MAX_LEN, CHUNK = 8, 64, 16


def _spec(**over):
    return R.Spec.from_config(dict(SMALL, **over))


def _cfg(spec: R.Spec, **over):
    """The program's config for ``spec``, computing in float32."""
    full = configs.get("deepseek-v2-lite")
    return dataclasses.replace(
        full, n_layers=spec.layers, first_dense_layers=spec.dense_layers,
        d_model=spec.d_model, n_heads=spec.heads, n_kv_heads=spec.heads,
        kv_lora_rank=spec.kv_rank, qk_nope_dim=spec.nope,
        qk_rope_dim=spec.rope, v_head_dim=spec.v_dim,
        dense_d_ff=spec.dense_ff, d_ff=spec.expert_ff,
        n_experts=spec.experts, experts_held=spec.experts_held,
        first_expert=spec.first_expert, top_k=spec.top_k,
        n_shared_experts=spec.shared, vocab=spec.vocab,
        dtype="float32", param_dtype="float32", **over).validate()


def _params(spec: R.Spec):
    """The reference's bf16 weights, in float32 for the program."""
    p = R.program_params(W.seed_key(SEED), spec, jnp.bfloat16)
    return jax.tree.map(lambda a: a.astype(jnp.float32), p)


def _rel(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def test_program_holds_the_reference_layout_and_sizes():
    spec = _spec()
    cfg = _cfg(spec)
    R.check_program(cfg, spec)
    key = jax.random.PRNGKey(0)
    W.check_layout(jax.eval_shape(lambda k: R.program_params(
        k, spec, jnp.float32), key), nn.unwrap(M.init_lm_shapes(key, cfg)))
    with pytest.raises(ValueError, match="configuration file states"):
        R.check_program(dataclasses.replace(cfg, norm_topk=True), spec)


# ------------------------------------------------------------------- YaRN
def test_yarn_frequencies_and_scale_in_closed_form():
    """DeepSeek-V2-Lite's 64 rope dims: pairs below the beta_fast
    correction dim (floor 10.47 = 10) keep theta ** (-2i/64), pairs above
    beta_slow's (ceil 22.51 = 23) take it over 40, linearly between; the
    score scale is 192 ** -0.5 * m ** 2, m = 0.1 * 0.707 * ln 40 + 1, and
    cos/sin are scaled by m / m = 1."""
    cfg = configs.get("deepseek-v2-lite")
    i = np.arange(32)
    base = 10000.0 ** (-2.0 * i / 64)
    ramp = np.clip((i - 10) / 13, 0, 1)
    want = base / 40 * ramp + base * (1 - ramp)
    got = A.yarn_freqs(jnp.asarray(base, jnp.float32), cfg.rope_yarn,
                       cfg.rope_theta)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)
    ref_spec = R.Spec.from_config(dict(
        SMALL, qk_rope_head_dim=64, qk_nope_head_dim=128))
    np.testing.assert_allclose(R.inv_freq(ref_spec), want, rtol=1e-12)
    m = 0.1 * 0.707 * math.log(40) + 1
    assert cfg.softmax_scale == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert R.softmax_scale(ref_spec) == pytest.approx(cfg.softmax_scale,
                                                      rel=1e-12)
    y = cfg.rope_yarn
    assert y.m(y.mscale) / y.m(y.mscale_all_dim) == 1.0
    # de-interleaved rope: pair (x0, x1) turns at the first frequency
    x = jnp.zeros((1, 1, 1, 64)).at[..., 1].set(1.0)
    out = A.mla_rope(x, jnp.asarray([3]), cfg)
    assert float(out[0, 0, 0, 32]) == pytest.approx(math.cos(3.0), rel=1e-6)
    assert float(out[0, 0, 0, 0]) == pytest.approx(-math.sin(3.0), rel=1e-6)


# ---------------------------------------------------------- absorbed form
def test_absorbed_decode_matches_the_expanded_form():
    """Prefill (keys and values expanded per head) then decode through the
    slot cache (latent space, the cache never expanded) gives the logits
    the expanded forward gives at the same positions."""
    spec = _spec()
    cfg = _cfg(spec)
    p = _params(spec)
    toks = jnp.asarray(np.random.default_rng(1).integers(
        0, spec.vocab, (2, 24)), jnp.int32)
    full, _ = M.forward(p, {"tokens": toks}, cfg)
    _, caches = M.prefill(p, {"tokens": toks[:, :20]}, cfg, max_len=32)
    assert set(caches) == {"latent", "len"}
    assert caches["latent"].shape == (3, 2, 32, 128)   # 32 + 8, lane-filled
    for t in range(20, 24):
        got, caches = M.decode_step(p, caches, toks[:, t], cfg)
        assert _rel(got, full[:, t]) < 1e-5, t


# ------------------------------------------------ serving vs the reference
def _serve(p, cfg, seqs, shared_pages: int):
    """Logits of every position after the first prompt chunk, through the
    paged latent store: sequence 0 chunked from empty, sequence 1 from its
    first ``shared_pages`` pages, which it shares with sequence 0 (a
    prefix hit), then both decoded together, teacher-forced."""
    ex = {"tokens": np.zeros((1, 8), np.int32)}
    n_pg = MAX_LEN // PS
    caches, axes = M.alloc_paged_caches(p, cfg, 2, MAX_LEN, PS, 2 * n_pg + 1,
                                        ex)
    pt = np.zeros((2, n_pg), np.int32)
    pt[0] = np.arange(1, n_pg + 1)
    pt[1, :shared_pages] = pt[0, :shared_pages]
    pt[1, shared_pages:] = np.arange(n_pg + 1, 2 * n_pg + 1 - shared_pages)
    chunk = jax.jit(functools.partial(M.prefill_chunk, cfg=cfg, axes=axes))
    decode = jax.jit(functools.partial(M.decode_step, cfg=cfg))
    prompt = [len(seqs[0]) - 4, len(seqs[1]) - 4]
    out = [dict(), dict()]
    for slot, start in ((0, 0), (1, shared_pages * PS)):
        caches = M.set_slot_lens(caches, slot, start, axes)
        for pos in range(start, prompt[slot], CHUNK):
            n = min(CHUNK, prompt[slot] - pos)
            buf = np.zeros((1, CHUNK), np.int32)
            buf[0, :n] = seqs[slot][pos:pos + n]
            last, caches, counts = chunk(p, caches, jnp.asarray(buf),
                                         jnp.asarray(pt[slot:slot + 1]),
                                         slot, n)
            out[slot][pos + n - 1] = np.asarray(last[0])
            assert int(counts["moe_rows"]) == \
                n * cfg.top_k * cfg.moe_layers
    for t in range(3):
        toks = jnp.asarray([seqs[s][prompt[s] + t] for s in (0, 1)],
                           jnp.int32)
        logits, caches = decode(p, caches, toks, pt=jnp.asarray(pt),
                                active=jnp.asarray([True, True]))
        for s in (0, 1):
            out[s][prompt[s] + t] = np.asarray(logits[s])
    return out


@pytest.mark.parametrize("use_pallas", [False, True])
def test_paged_serving_matches_the_reference(use_pallas):
    """Chunked prefill into the paged latent store, a second sequence
    starting from the first's shared pages, and absorbed decode of both:
    logits at every read position agree with the reference's full forward
    in float32."""
    spec = _spec()
    cfg = _cfg(spec, use_pallas=use_pallas)
    rng = np.random.default_rng(2)
    a = rng.integers(0, spec.vocab, 44).astype(np.int32)
    b = np.concatenate([a[:3 * PS], rng.integers(0, spec.vocab, 14)
                        ]).astype(np.int32)
    got = _serve(_params(spec), cfg, [a, b], shared_pages=3)
    reads = [np.asarray(sorted(g)) for g in got]
    want = R.logits(SEED, spec, [a, b], reads, pad_to=MAX_LEN)
    for s in (0, 1):
        for i, r in enumerate(reads[s]):
            assert _rel(got[s][r], want[s][i]) < 2e-4, (s, r)


def test_fused_decode_matches_the_gathered_read():
    """Paged absorbed decode through the fused kernel (``use_pallas``)
    against the gathered latent view through ``_mla_absorbed``, on the
    same noisy latent store, with an idle row: the same logits to float32
    rounding, and the same tokens."""
    spec = _spec()
    p = _params(spec)
    ex = {"tokens": np.zeros((1, 8), np.int32)}
    n_pg = MAX_LEN // PS
    rng = np.random.default_rng(6)
    pt = jnp.asarray(rng.permutation(np.arange(1, 3 * n_pg + 1))
                     .reshape(3, n_pg).astype(np.int32))
    caches, _ = M.alloc_paged_caches(p, _cfg(spec), 3, MAX_LEN, PS,
                                     3 * n_pg + 1, ex)
    noise = rng.standard_normal(caches["latent"].shape).astype(np.float32)
    lens = np.tile([37, 0, 8], (spec.layers, 1)).astype(np.int32)
    out = {}
    for use_pallas in (False, True):
        cfg = _cfg(spec, use_pallas=use_pallas)
        caches = {"latent": jnp.asarray(noise), "len": jnp.asarray(lens)}
        logits, new = jax.jit(functools.partial(M.decode_step, cfg=cfg))(
            p, caches, jnp.asarray([3, 4, 5], jnp.int32), pt=pt,
            active=jnp.asarray([True, False, True]))
        out[use_pallas] = (np.asarray(logits)[[0, 2]],
                           {n: np.asarray(v) for n, v in new.items()})
    (want, w_new), (got, g_new) = out[False], out[True]
    assert _rel(got, want) < 1e-5
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))
    np.testing.assert_array_equal(g_new["len"], w_new["len"])
    # layer 0's latent comes before any attention: written bit for bit
    np.testing.assert_array_equal(g_new["latent"][0], w_new["latent"][0])


# ------------------------------------------------------- the expert share
def _moe_layer(spec: R.Spec):
    """Layer 1's MoE weights for ``spec``'s share, in float32."""
    w = R.layer(W.seed_key(SEED), 1, spec, jnp.float32, moe=True)
    return w["ffn"]


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four shares of 2 of 8 experts, each routing over all 8: their
    outputs, with the shared experts (which every device computes alike)
    counted once, add up to the uncut layer, the program's and the
    reference's; every routed row lands in exactly one share."""
    full = _spec(n_routed_experts=8, first_held_expert=0)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((2, 5, 64)),
                    jnp.float32)
    cfg_full = _cfg(full)
    y_full, aux_full = moe_mod.moe(_moe_layer(full), x, cfg_full)
    shared = moe_mod.mlp_mod.mlp(_moe_layer(full)["shared"], x,
                                 moe_mod.shared_cfg(cfg_full))
    total, rows = jnp.zeros_like(x), 0
    for s in range(4):
        part = _spec(n_routed_experts=2, first_held_expert=2 * s)
        y, aux = moe_mod.moe(_moe_layer(part), x, _cfg(part))
        total = total + (y - shared)
        rows += int(aux["moe_local_rows"])
        assert int(aux["moe_rows"]) == 10 * full.top_k
    total = total + shared
    assert rows == int(aux_full["moe_local_rows"]) == 10 * full.top_k
    assert _rel(total, y_full) < 1e-5
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda h: R._experts(h, _moe_layer(full), full,
                                             None))(x)
    assert _rel(y_full, want) < 1e-5


@pytest.mark.parametrize("norm_topk,shared", [(True, 0), (False, 2)],
                         ids=["renormalized", "shared-experts"])
def test_served_moe_is_the_capacity_dispatch_with_nothing_dropped(
        norm_topk, shared):
    """Every MoE config serves (``dropless``) through the share path, every
    expert held: it computes what training's capacity dispatch computes
    when the capacity drops nothing, and counts every routed row as
    local."""
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="m", family="moe", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=48, vocab=64,
                      n_experts=4, top_k=2, capacity_factor=2.0,
                      n_shared_experts=shared, norm_topk=norm_topk,
                      dtype="float32").validate()
    ff = nn.unwrap(moe_mod.init_moe(jax.random.PRNGKey(7), cfg))
    x = jnp.asarray(np.random.default_rng(8).standard_normal((2, 6, 32)),
                    jnp.float32)
    served, aux = moe_mod.moe(ff, x, cfg, dropless=True)
    trained, _ = moe_mod.moe(ff, x, cfg)      # cap = 12 * 2 / 4 * 2 = t
    assert _rel(served, trained) < 1e-5
    assert int(aux["moe_local_rows"]) == int(aux["moe_rows"]) == 12 * 2


def test_moe_serves_the_same_tokens_paged_and_contiguous():
    """Whole-prompt prefill is dropless too: at a capacity factor under
    which training's dispatch drops rows, the contiguous engine (prefill
    then decode) and the paged one (chunks then decode) serve the same
    tokens."""
    from repro.models.config import ModelConfig
    cfg = ModelConfig(name="m", family="moe", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=48, vocab=64,
                      n_experts=4, top_k=2, capacity_factor=0.5,
                      dtype="float32").validate()
    params = nn.unwrap(M.init_lm(jax.random.PRNGKey(9), cfg))
    rng = np.random.default_rng(10)
    prompts = [rng.integers(1, 64, n).astype(np.int32) for n in (6, 13, 9)]

    def serve(paged: bool):
        eng = ContinuousEngine(params, cfg, ServeConfig(
            max_len=32, capacity=2, paged=paged, page_size=8,
            prefill_chunk=8 if paged else None))
        for pr in prompts:
            eng.submit(pr, 4)
        done = eng.run(max_steps=200)
        return [done[u].tolist() for u in range(len(prompts))]

    assert serve(paged=False) == serve(paged=True)


def test_expert_share_computes_only_valid_rows():
    """Rows of invalid tokens (padding, idle decode rows) add nothing and
    are not counted; the gates are not renormalized."""
    spec = _spec()
    cfg = _cfg(spec)
    ff = _moe_layer(spec)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((1, 6, 64)),
                    jnp.float32)
    valid = jnp.asarray([[True, True, True, False, False, True]])
    y, aux = moe_mod.moe(ff, x, cfg, valid=valid)
    y_all, aux_all = moe_mod.moe(ff, x, cfg)
    keep = np.asarray(valid[0])
    np.testing.assert_allclose(np.asarray(y)[0, keep],
                               np.asarray(y_all)[0, keep], rtol=1e-6)
    assert int(aux["moe_rows"]) == 4 * spec.top_k
    assert 0 < int(aux["moe_local_rows"]) < int(aux_all["moe_local_rows"])
    renorm = moe_mod.moe(ff, x, dataclasses.replace(cfg, norm_topk=True))[0]
    assert _rel(renorm, y_all) > 1e-2


def test_engine_counts_routed_rows():
    """The engine adds each decode and chunk step's routed-row counts to
    its stats: every prompt token and every decoded token, top_k pairs in
    each MoE layer, a share of them routed to the held experts."""
    spec = _spec()
    cfg = _cfg(spec, use_pallas=True)
    eng = ContinuousEngine(_params(spec), cfg, ServeConfig(
        max_len=MAX_LEN, capacity=2, paged=True, page_size=PS,
        prefill_chunk=CHUNK, prefix_cache=True))
    rng = np.random.default_rng(5)
    a = rng.integers(0, spec.vocab, 30).astype(np.int32)
    b = np.concatenate([a[:2 * PS], rng.integers(0, spec.vocab, 9)])
    eng.submit(a, 5)
    eng.run(max_steps=100)
    eng.submit(b, 4)
    eng.run(max_steps=100)
    st = eng.stats
    computed = (st["prefill_tokens"] + st["tokens_out"] - st["admitted"])
    assert st["prefix_hits"] == 1
    assert st["moe_rows"] == computed * cfg.top_k * cfg.moe_layers
    assert 0 < st["moe_local_rows"] < st["moe_rows"]
