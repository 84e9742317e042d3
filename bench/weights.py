"""Random dense decoder-LM weights made from ``--seed``, on the device.

Every weight is a point of an integer grid, ``w = i * step`` with ``i``
uniform in [-127, 127] and ``step`` set so that the standard deviation is
the usual ``fan_in ** -0.5``.  One correctly rounded float32 multiply and
one cast make each value, so the program's stacked weights (one jitted call
over all layers) and the reference's layer-by-layer weights are the same
numbers bit for bit, on any backend.

The tree has the serving program's parameter layout (a ``blocks`` stack
over layers); :func:`check_layout` holds it to the program's own shapes.
Where the configuration ties its output head to the embedding, the
program's separate ``lm_head`` holds the embedding's transpose, so the
model served is the tied one.
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

LEVELS = 127
_TOP, _LAYER = 1, 2          # key tags: model-level tensors, layer tensors


@dataclasses.dataclass(frozen=True)
class Spec:
    """The published sizes of a dense decoder LM, as a configuration file
    states them."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qk_norm: bool
    rms_eps: float
    rope_theta: float
    tied: bool = False          # output head = embedding transposed

    @classmethod
    def from_config(cls, c: dict) -> "Spec":
        return cls(layers=c["num_hidden_layers"], d_model=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
                   d_ff=c["intermediate_size"], vocab=c["vocab_size"],
                   qk_norm=bool(c.get("qk_norm", False)),
                   rms_eps=float(c["rms_norm_eps"]),
                   rope_theta=float(c["rope_theta"]),
                   tied=bool(c.get("tie_word_embeddings", False)))


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative seed below 2**64."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _grid(key, shape, std: float, dtype, offset: float = 0.0):
    i = jax.random.randint(key, shape, -LEVELS, LEVELS + 1, jnp.int32)
    step = jnp.float32(std * math.sqrt(3.0) / LEVELS)
    return (jnp.float32(offset) + i.astype(jnp.float32) * step).astype(dtype)


def _gain(key, n: int, dtype):
    """RMSNorm gains near 1, not all 1, so a norm that ignores its gains
    shows in the logits."""
    return _grid(key, (n,), 0.1, dtype, offset=1.0)


def layer(key, l, s: Spec, dtype) -> dict:
    """Layer ``l``'s weights (``l`` may be traced)."""
    k = jax.random.fold_in(jax.random.fold_in(key, _LAYER), l)
    ks = [jax.random.fold_in(k, i) for i in range(11)]
    d, h, hkv, hd, f = s.d_model, s.heads, s.kv_heads, s.head_dim, s.d_ff
    attn = {"wq": _grid(ks[0], (d, h, hd), d ** -0.5, dtype),
            "wk": _grid(ks[1], (d, hkv, hd), d ** -0.5, dtype),
            "wv": _grid(ks[2], (d, hkv, hd), d ** -0.5, dtype),
            "wo": _grid(ks[3], (h, hd, d), (h * hd) ** -0.5, dtype)}
    if s.qk_norm:
        attn["q_norm"] = _gain(ks[4], hd, dtype)
        attn["k_norm"] = _gain(ks[5], hd, dtype)
    return {"ln1": _gain(ks[6], d, dtype), "attn": attn,
            "ln2": _gain(ks[7], d, dtype),
            "ffn": {"w_gate": _grid(ks[8], (d, f), d ** -0.5, dtype),
                    "w_up": _grid(ks[9], (d, f), d ** -0.5, dtype),
                    "w_down": _grid(ks[10], (f, d), f ** -0.5, dtype)}}


def top(key, s: Spec, dtype) -> dict:
    """Embedding, final norm and output head.  A tied embedding has the
    head's scale, ``d_model ** -0.5``."""
    k = jax.random.fold_in(key, _TOP)
    ks = [jax.random.fold_in(k, i) for i in range(3)]
    head = s.d_model ** -0.5
    embed = _grid(ks[0], (s.vocab, s.d_model), head if s.tied else 1.0,
                  dtype)
    return {"embed": embed, "ln_f": _gain(ks[1], s.d_model, dtype),
            "lm_head": embed.T if s.tied else
            _grid(ks[2], (s.d_model, s.vocab), head, dtype)}


def program_params(key, s: Spec, dtype) -> dict:
    """All weights in the serving layout: one call, layers stacked."""
    out = top(key, s, dtype)
    out["blocks"] = jax.vmap(lambda l: layer(key, l, s, dtype))(
        jnp.arange(s.layers))
    return out


def make(seed: int, s: Spec, dtype, shardings=None):
    """The served weights, made on the device in one jitted call (in their
    shards where ``shardings`` is given).  The key is an argument, so one
    compiled program serves every seed."""
    fn = jax.jit(lambda k: program_params(k, s, dtype),
                 out_shardings=shardings)
    return fn(seed_key(seed))


def check_layout(params_shapes, program_shapes) -> None:
    """Raise unless the weights have exactly the program's tree, shapes and
    dtypes."""
    a = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params_shapes)
    b = jax.tree.map(lambda x: (x.shape, str(x.dtype)), program_shapes)
    if a != b:
        raise ValueError(f"benchmark weights do not match the program's "
                         f"parameter layout:\n  bench:   {a}\n  program: {b}")
