"""A planted fault: the dense model module with its reference's per-head
q/k norm dropped.  The program still applies the norm, so the tokens it
serves must read as not correct against this reference."""

import dataclasses

from bench.reference import dense_lm
from bench.reference.dense_lm import (Spec, check_program,  # noqa: F401
                                      chunk_flops, kv_read_bytes, make,
                                      program_params, token_flops)


def logits(seed, spec, seqs, reads, *, pad_to, quant=None):
    return dense_lm.logits(seed, dataclasses.replace(spec, qk_norm=False),
                           seqs, reads, pad_to=pad_to, quant=quant)
