"""A model module whose family the dense program does not serve: it holds
the program to a 1,024-token attention window."""

from bench.reference.dense_lm import (Spec, chunk_flops,  # noqa: F401
                                      kv_read_bytes, logits, make,
                                      program_params, token_flops)

WINDOW = 1024


def check_program(cfg, spec):
    if cfg.window != WINDOW:
        raise ValueError(f"program config {cfg.name!r} attends over window "
                         f"{cfg.window}, the configuration file states "
                         f"{WINDOW}")
