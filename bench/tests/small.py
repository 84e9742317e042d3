"""Small configurations that run a whole cell on the CPU in seconds, for
the benchmark's own tests (``bench/tests``)."""

from __future__ import annotations

import copy
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[2]

CONFIG = {
    "num_hidden_layers": 2, "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 256, "rope_theta": 1000000, "rms_norm_eps": 1e-06,
    "qk_norm": True, "tie_word_embeddings": True,
    "model": "bench/reference/dense_lm.py",
    "program": {"arch": "qwen3-1.7b", "overrides": {
        "n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab": 256, "rope_theta": 1000000.0,
        "param_dtype": "bfloat16", "dtype": "bfloat16", "use_pallas": True}},
    "engine": {"capacity": 4, "page_size": 8, "prefill_chunk": 32,
               "max_len": 160, "num_pages": 81, "prefix_cache": True},
    "limits": {"sample_requests": 3, "served_logit_gap": 0.05,
               "attention_rel_err": 0.02},
    "torch_dtype": "bfloat16",
}

MIXES = {
    "chat": {"kind": "poisson", "rate_per_s": 6.0, "order_seed": 12,
             "prompt_tokens": {"median": 60, "sigma": 0.5, "min": 40,
                               "max": 120},
             "output_tokens": {"median": 8, "sigma": 0.5, "min": 4,
                               "max": 16}},
    "sessions": {"kind": "sessions", "session_rate_per_s": 2.0,
                 "order_seed": 12, "ramp_s": 1.0, "system_prompts": 2,
                 "system_tokens": 48, "turns": [2, 3], "user_tokens": [8, 24],
                 "output_tokens": {"median": 6, "sigma": 0.5, "min": 4,
                                   "max": 10},
                 "think_s_mean": 0.2},
    "sip-tune": {"kind": "tune", "kernel": "flash_attention_causal",
                 "batch": 1, "seq": 64, "rounds": 2, "search_seed": 0,
                 "energy": "costmodel",
                 "knob_prob": 0.5, "cooling": 3.0, "step_samples": 1,
                 "final_samples": 2},
}


def loader(config=None, mixes=None):
    """A ``load`` for ``bench.run.main``: the real BENCHMARK.json's metrics
    over one small cell per mix."""
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    config = config or CONFIG
    mixes = mixes or MIXES
    bench = copy.deepcopy(bench)
    traffic_of = {c["name"]: c["traffic"] for c in bench["workloads"]}
    bench["configs"] = [{"name": "small", "file": "small.json"}]
    bench["workloads"] = [{"name": f"small.{m}", "config": "small",
                           "traffic": m, "chips": 1} for m in mixes]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:      # the small cell of each real cell's mix
            m["workloads"] = [f"small.{traffic_of[c]}"
                              for c in m["workloads"]]
    files = {"BENCHMARK.json": bench, "small.json": config}
    files.update({f"bench/traffic/{k}.json": v for k, v in mixes.items()})
    return lambda rel: copy.deepcopy(files[rel])
