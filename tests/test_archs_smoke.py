"""Per-assigned-architecture smoke tests (deliverable f).

Each of the 10 archs is instantiated at a REDUCED same-family config
(models/config.smoke_variant) and runs one forward + one train step on CPU,
asserting output shapes and no NaNs.  The FULL configs are exercised only via
the dry-run (ShapeDtypeStruct, no allocation) — tests/test_dryrun_smoke.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch import steps
from repro.models import model as M
from repro.models import modules as nn
from repro.optim import adamw

B, S = 2, 32
RNG = np.random.default_rng(7)


def smoke_inputs(cfg):
    out = {"labels": jnp.asarray(RNG.integers(0, cfg.vocab, (B, S)), jnp.int32)}
    if cfg.family == "enc_dec":
        out["tokens"] = jnp.asarray(RNG.integers(0, cfg.vocab, (B, S)), jnp.int32)
        out["enc_embeds"] = jnp.asarray(
            RNG.standard_normal((B, cfg.enc_len, cfg.d_model)), jnp.float32)
    elif cfg.input_mode == "embeddings":
        out["embeds"] = jnp.asarray(
            RNG.standard_normal((B, S, cfg.d_model)), jnp.float32)
    else:
        out["tokens"] = jnp.asarray(RNG.integers(0, cfg.vocab, (B, S)), jnp.int32)
    return out


@pytest.mark.parametrize("arch", configs.arch_names())
class TestArchSmoke:
    def test_full_config_exact(self, arch):
        """The registered config carries the assignment's exact numbers."""
        cfg = configs.get(arch)
        expected = {
            "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
            "llama4-scout-17b-16e": (48, 5120, 40, 8, 8192, 202048),
            "llava-next-34b": (60, 7168, 56, 8, 20480, 64000),
            "mamba2-2.7b": (64, 2560, 0, 0, 0, 50280),
            "zamba2-7b": (81, 3584, 32, 32, 14336, 32000),
            "seamless-m4t-large-v2": (48, 1024, 16, 16, 8192, 256206),
            "qwen3-4b": (36, 2560, 32, 8, 9728, 151936),
            "internlm2-20b": (48, 6144, 48, 8, 16384, 92544),
            "qwen3-1.7b": (28, 2048, 16, 8, 6144, 151936),
            "h2o-danube-1.8b": (24, 2560, 32, 8, 6912, 32000),
            "deepseek-v2-lite": (27, 2048, 16, 16, 1408, 102400),
        }[arch]
        got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
               cfg.d_ff, cfg.vocab)
        assert got == expected, (arch, got, expected)

    def test_smoke_forward(self, arch):
        cfg = configs.get_smoke(arch)
        params = nn.unwrap(M.init_lm(jax.random.PRNGKey(0), cfg))
        logits, _ = M.forward(params, smoke_inputs(cfg), cfg)
        assert logits.shape == (B, S, cfg.vocab)
        assert np.all(np.isfinite(np.asarray(logits, np.float32))), arch

    def test_smoke_train_step(self, arch):
        cfg = configs.get_smoke(arch)
        params = nn.unwrap(M.init_lm(jax.random.PRNGKey(0), cfg))
        opt = adamw.init_opt_state(params)
        p2, o2, metrics = jax.jit(
            lambda p, o, b: steps.train_step(
                p, o, b, cfg=cfg, opt_cfg=adamw.OptConfig(warmup_steps=1)),
        )(params, opt, smoke_inputs(cfg))
        assert np.isfinite(metrics["loss"]), arch
        assert int(o2["step"]) == 1
        # parameters actually moved
        delta = sum(float(jnp.sum(jnp.abs(a.astype(jnp.float32) -
                                          b.astype(jnp.float32))))
                    for a, b in zip(jax.tree.leaves(params),
                                    jax.tree.leaves(p2)))
        assert delta > 0, arch


class TestExtraArchProperties:
    def test_swa_arch_has_window(self):
        assert configs.get("h2o-danube-1.8b").window == 4096

    def test_qwen_qk_norm(self):
        assert configs.get("qwen3-4b").qk_norm
        assert configs.get("qwen3-1.7b").qk_norm

    def test_moe_expert_counts(self):
        dbrx = configs.get("dbrx-132b")
        assert (dbrx.n_experts, dbrx.top_k) == (16, 4)
        scout = configs.get("llama4-scout-17b-16e")
        assert (scout.n_experts, scout.top_k) == (16, 1)

    def test_long_context_applicability(self):
        runnable = {n for n, _, s, ok, _ in configs.cells()
                    if s.name == "long_500k" and ok}
        assert runnable == {"mamba2-2.7b", "zamba2-7b", "h2o-danube-1.8b"}

    def test_40_cells_enumerated(self):
        assert len(list(configs.cells())) == 40
