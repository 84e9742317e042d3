"""The dense family's model module under another name: a family that a
configuration file reaches by a new file alone, with no edit to a file the
harness already has."""

from bench.reference.dense_lm import (Spec, check_program,  # noqa: F401
                                      chunk_flops, kv_read_bytes, logits,
                                      make, program_params, token_flops)
