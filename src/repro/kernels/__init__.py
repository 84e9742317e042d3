"""Kernel packages.  Each compute hot-spot ships ``kernel.py`` (the Pallas
emission), ``ref.py`` (the oracle), and an integration module (``ops.py`` /
``pallas_ops.py``) that registers a declarative ``KernelSpec`` with
``repro.core.registry`` at import time.

Adding a kernel touches ONLY its own package: drop a new directory with an
integration module and :func:`load_all` discovers it — the tuning driver,
smoke CI, and deployment resolve it by name with no launcher edits.
"""

from __future__ import annotations

import importlib
import importlib.util
import pkgutil

import jax


def interpret_mode() -> bool:
    """Whether a Pallas kernel called now runs in interpret mode: everywhere
    but on a TPU.  Kernels ask at call time (never at import), so importing a
    kernel module touches no backend."""
    return jax.default_backend() != "tpu"


# integration modules probed inside each kernel package, in import order
_INTEGRATION_MODULES = ("ops", "pallas_ops")


def load_all() -> list[str]:
    """Import every kernel package's integration module(s), registering
    their KernelSpecs.  Returns the registered kernel names.

    Fails loudly (instead of silently dropping a kernel from tuning/CI)
    when a kernel package has no integration module or registers nothing.
    """
    from repro.core.registry import registry

    for info in pkgutil.iter_modules(__path__):
        if not info.ispkg:
            continue
        found = False
        for mod in _INTEGRATION_MODULES:
            full = f"{__name__}.{info.name}.{mod}"
            if importlib.util.find_spec(full) is not None:
                importlib.import_module(full)
                found = True
        if not found:
            raise RuntimeError(
                f"kernel package {info.name!r} has no integration module "
                f"({' / '.join(_INTEGRATION_MODULES)})")
        prefix = f"{__name__}.{info.name}"
        if not any(s.module == prefix or s.module.startswith(prefix + ".")
                   for s in registry.specs()):
            raise RuntimeError(
                f"kernel package {info.name!r} registers no KernelSpec — "
                f"decorate its build factory with @sip_kernel (or call "
                f"registry.register) in its integration module")
    return registry.names()
