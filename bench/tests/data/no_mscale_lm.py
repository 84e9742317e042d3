"""The DeepSeek-V2 module with YaRN's ``mscale ** 2`` dropped from its
reference's softmax scale (scores at (nope + rope) ** -0.5 alone; cos and
sin keep their multiplier of 1): a lost part of the mathematics, which the
small limit must catch."""

import dataclasses

from bench.reference import deepseek_v2 as _R
from bench.reference.deepseek_v2 import (Spec, check_program,  # noqa: F401
                                         chunk_flops, kv_read_bytes, make,
                                         program_params, token_flops)


def logits(seed, spec, seqs, reads, *, pad_to, quant=None):
    # m(0) = 1 for both: the score scale loses m(mscale_all_dim) ** 2, and
    # the rope multiplier m(mscale) / m(mscale_all_dim) stays 1
    yarn = spec.yarn[:4] + (0.0, 0.0)
    return _R.logits(seed, dataclasses.replace(spec, yarn=yarn), seqs,
                     reads, pad_to=pad_to, quant=quant)
