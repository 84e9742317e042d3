"""Operations and bytes the algorithms need, computed from shapes.

These count the work a computation requires, not what an implementation
happens to do: padding, masked-out positions and recomputation count for
nothing.  A multiply-add is two operations.
"""

from __future__ import annotations

from bench.weights import Spec


def matmul_params_per_layer(s: Spec) -> int:
    d, hd = s.d_model, s.head_dim
    attn = d * s.heads * hd * 2 + d * s.kv_heads * hd * 2   # q, o; k, v
    return attn + 3 * d * s.d_ff                            # gate, up, down


def token_flops(s: Spec, keys: int, head: bool = True) -> float:
    """One token through the whole model, attending to ``keys`` positions
    (itself included); ``head``: whether its output logits are needed."""
    f = 2.0 * matmul_params_per_layer(s) * s.layers
    f += 4.0 * s.heads * s.head_dim * keys * s.layers       # QK^T and PV
    if head:
        f += 2.0 * s.d_model * s.vocab
    return f


def chunk_flops(s: Spec, start: int, n: int, last: bool) -> float:
    """A prefill chunk of ``n`` real tokens at positions start..start+n-1;
    the output head is needed only for the prompt's final token."""
    keys = n * start + n * (n + 1) // 2
    f = 2.0 * matmul_params_per_layer(s) * s.layers * n
    f += 4.0 * s.heads * s.head_dim * keys * s.layers
    if last:
        f += 2.0 * s.d_model * s.vocab
    return f


def causal_attention_flops(b: int, hq: int, s: int, d: int) -> float:
    """Causal self-attention forward: QK^T and PV over the S(S+1)/2
    unmasked pairs of each head."""
    return 4.0 * b * hq * d * s * (s + 1) / 2


def attention_bytes(b: int, hq: int, hkv: int, s: int, d: int,
                    itemsize: int) -> float:
    """Least HBM traffic: read q, k, v once, write o once."""
    return float(itemsize * b * s * d * (2 * hq + 2 * hkv))


def gather_bytes(b: int, pages: int, page_size: int, kv_heads: int,
                 head_dim: int, itemsize: int) -> float:
    """One paged gather of ``pages`` pages for each of ``b`` rows: each page
    read once and written once into the contiguous view."""
    return 2.0 * b * pages * page_size * kv_heads * head_dim * itemsize
