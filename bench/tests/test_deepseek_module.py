"""The DeepSeek-V2 model module (``bench/reference/deepseek_v2.py``) served
through the harness at a small size: MLA over the paged latent cache and
the expert share, held to the float32 reference.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import json
from types import SimpleNamespace

import pytest

from bench.tests import small
from bench.tests.test_correct import _run

MODULE = "bench/reference/deepseek_v2.py"

#: a small DeepSeek-V2 with the published YaRN settings and an expert share
#: (experts 2-5 of 8), as its configuration file states it
CONFIG = {
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "n_routed_experts": 4, "routed_experts_published": 8,
    "first_held_expert": 2, "num_experts_per_tok": 3, "n_shared_experts": 2,
    "norm_topk_prob": False, "vocab_size": 256, "rms_norm_eps": 1e-06,
    "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "q_lora_rank": None, "hidden_act": "silu", "scoring_func": "softmax",
    "topk_method": "greedy", "routed_scaling_factor": 1, "n_group": 1,
    "topk_group": 1, "moe_layer_freq": 1, "attention_bias": False,
    "tie_word_embeddings": False, "torch_dtype": "bfloat16",
    "model": MODULE,
    "program": {"arch": "deepseek-v2-lite", "overrides": {
        "n_layers": 3, "first_dense_layers": 1, "d_model": 64, "n_heads": 4,
        "n_kv_heads": 4, "kv_lora_rank": 32, "qk_nope_dim": 16,
        "qk_rope_dim": 8, "v_head_dim": 16, "dense_d_ff": 96, "d_ff": 32,
        "n_experts": 8, "experts_held": 4, "first_expert": 2, "top_k": 3,
        "n_shared_experts": 2, "vocab": 256, "param_dtype": "bfloat16",
        "dtype": "bfloat16", "use_pallas": True}},
    "engine": dict(small.CONFIG["engine"]),
    "limits": {"sample_requests": 3, "served_logit_gap": 0.05},
}


def _with(model):
    return dict(CONFIG, model=model)


@pytest.mark.parametrize("cell", ["small.chat", "small.sessions"])
def test_served_correctly(capsys, cell):
    out = _run(capsys, cell, load=small.loader(config=CONFIG))
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0


def test_a_scale_without_mscale_is_not_correct(capsys):
    """The reference with YaRN's mscale squared dropped from its score
    scale: the served tokens leave it by more than the limit."""
    load = small.loader(config=_with("bench/tests/data/no_mscale_lm.py"))
    out = _run(capsys, "small.sessions", load=load)
    assert not out["correct"], out["checks"]


def test_a_program_without_the_share_is_refused():
    from bench import run
    prog = dict(CONFIG["program"], overrides=dict(
        CONFIG["program"]["overrides"], experts_held=8, first_expert=0))
    with pytest.raises(ValueError) as e:
        run.main(["--workload", "small.chat", "--seed", "5", "--seconds",
                  "1", "--trace", "0"], require_chip=False,
                 load=small.loader(config=dict(CONFIG, program=prog)))
    assert "configuration file states" in str(e.value)


def _lite():
    from bench.reference import deepseek_v2 as m
    with open(small.ROOT / "bench" / "configs" / "deepseek-v2-lite.json") as f:
        return m, m.Spec.from_config(json.load(f))


def test_counts_by_hand():
    """Cache bytes: 27 layers x (512 + 64) x 2 B a token, in whole pages;
    a decoded token's FLOPs: projections in the absorbed form, the
    expected 6 x 16/64 = 1.5 held experts, shared and dense MLPs, head, and
    2 x 16 heads x (2 x 512 + 64) a cached key and layer."""
    m, s = _lite()
    page = 27 * 16 * 576 * 2
    assert page == 497_664
    assert m.kv_read_bytes(s, 1, 16) == page
    assert m.kv_read_bytes(s, 17, 16) == 2 * page
    d = 2048
    attn = (d * 16 * 192 + d * 576 + 16 * 128 * 512 + 16 * 512 * 128
            + 16 * 128 * d)
    moe = d * 64 + 1.5 * 3 * d * 1408 + 3 * d * 2816
    mm = 2 * (27 * attn + 3 * d * 10944 + 26 * moe)
    head = 2 * d * 102400
    assert m.token_flops(s, 1000) == pytest.approx(
        mm + head + 2 * 16 * 1000 * 1088 * 27, rel=1e-12)
    assert m.chunk_flops(s, 256, 256, False) == pytest.approx(
        256 * mm + 2 * 16 * (256 * 256 + 256 * 257 // 2) * 1088 * 27,
        rel=1e-12)


def test_rows_per_local_expert_by_hand():
    from bench import run
    _, s = _lite()
    read = run.reader(small.ROOT, "moe.rows_per_local_expert")
    w = SimpleNamespace(spec=s, counters={
        "moe_local_rows": 16 * 26 * 10 * 3, "decode_steps": 8,
        "chunk_steps": 2})
    assert read(w) == 3.0
    w.counters = {"decode_steps": 8}
    assert read(w) is None
