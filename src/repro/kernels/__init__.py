"""Pallas TPU kernels for the engine's compute hot spots.

Each kernel package ships three modules:
  kernel.py -- pl.pallas_call body + BlockSpec tiling (TPU target)
  ops.py    -- jit'd public wrapper with backend switch ("pallas" |
               "interpret" | "jnp"); models/engine call these
  ref.py    -- pure-jnp oracle used for validation and as the jnp backend

Backend selection is centralized in :mod:`repro.kernels.registry`: a typed
:class:`~repro.kernels.registry.KernelBackend` enum, auto-resolution
(``pallas`` on TPU, ``jnp`` elsewhere, ``REPRO_KERNEL_BACKEND`` env
override) and a per-op dispatch table the ops wrappers register into.
``resolve_backend`` raises ``ValueError`` on unknown names — there is no
silent fallback.

Ops listed in ``registry.JNP_ONLY_OPS`` (the ELL row gathers) ship no
kernel.py and run their jnp arm on every backend. On CPU, tests check the
kernel bodies with interpret=True against ref.py across shape/dtype
sweeps; ``tests/test_tpu_compile.py`` compiles every Pallas arm for a
described TPU v5e at chip-sized widths.
"""
from .registry import (KernelBackend, dispatch, register_op,  # noqa: F401
                       registered_ops, resolve_backend)

DEFAULT_BACKEND = KernelBackend.JNP.value

__all__ = ["KernelBackend", "resolve_backend", "register_op", "dispatch",
           "registered_ops", "DEFAULT_BACKEND"]
