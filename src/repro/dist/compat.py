"""``jax.shard_map`` under the one name the repository calls.

Every call site (and the multi-device test scripts) goes through this
wrapper, so the replication-check argument is spelled in one place."""

from __future__ import annotations

from typing import Any, Callable

import jax


def shard_map(f: Callable[..., Any], mesh, in_specs, out_specs,
              check_vma: bool = True):
    """``jax.shard_map`` with keyword arguments."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)
