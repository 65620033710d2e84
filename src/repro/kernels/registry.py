"""Unified kernel-backend registry: one typed enum, one dispatch table.

Every compute hot spot the engine can route through a Pallas kernel is
registered here as a named *op* with three interchangeable
implementations:

  ``pallas``    -- the TPU kernel (pl.pallas_call; fails to lower on CPU)
  ``interpret`` -- the same kernel body under the Pallas interpreter
                   (CPU-runnable, bit-equal to ``pallas``; the CI parity
                   backend, not a performance proxy)
  ``jnp``       -- the pure-jnp segment-op reference twin (the default
                   everywhere off-TPU; property-tested bit-equal)

The ops in :data:`JNP_ONLY_OPS` have no Pallas arm: they run their
``jnp`` arm under every backend. That is a static choice, listed with its
reason, never a run-time probe or fallback.

Backend resolution order (``resolve_backend``):

  1. an explicit value (string or :class:`KernelBackend`) wins;
  2. else the ``REPRO_KERNEL_BACKEND`` environment variable;
  3. else auto: ``pallas`` on TPU, ``jnp`` elsewhere.

Unknown names raise ``ValueError`` listing the valid backends — there is
deliberately no silent fallback (misspelling "pallas" must not quietly
run the reference path).

Op tables self-register when a kernel package's ``ops`` module imports;
:func:`dispatch` lazily imports the owning module, so callers never need
to pre-import kernel packages.
"""
from __future__ import annotations

import enum
import importlib
import os
from typing import Callable, Optional, Union

__all__ = ["KernelBackend", "BackendLike", "resolve_backend", "register_op",
           "dispatch", "registered_ops", "op_manifest", "ENV_VAR",
           "JNP_ONLY_OPS"]

ENV_VAR = "REPRO_KERNEL_BACKEND"

BackendLike = Union["KernelBackend", str, None]


class KernelBackend(str, enum.Enum):
    """Typed kernel-backend selector (str subclass: compares to its value)."""

    PALLAS = "pallas"
    INTERPRET = "interpret"
    JNP = "jnp"

    @classmethod
    def coerce(cls, value: Union["KernelBackend", str]) -> "KernelBackend":
        if isinstance(value, cls):
            return value
        try:
            return cls(str(value).lower())
        except ValueError:
            raise ValueError(
                f"unknown kernel backend {value!r}; valid backends: "
                f"{' | '.join(b.value for b in cls)}") from None

    @property
    def uses_kernel(self) -> bool:
        """True when the Pallas kernel body runs (compiled or interpreted)."""
        return self is not KernelBackend.JNP

    def __str__(self) -> str:  # str(Enum) would print "KernelBackend.JNP"
        return self.value


def resolve_backend(backend: BackendLike = None) -> KernelBackend:
    """Resolve an explicit/env/auto backend choice to a KernelBackend.

    Raises ``ValueError`` (listing the valid names) on unknown values —
    including an unknown ``REPRO_KERNEL_BACKEND`` — so a typo surfaces at
    config time, not as a silently different code path.
    """
    if backend is None:
        backend = os.environ.get(ENV_VAR) or None
    if backend is None:
        import jax
        return (KernelBackend.PALLAS if jax.default_backend() == "tpu"
                else KernelBackend.JNP)
    return KernelBackend.coerce(backend)


# ---------------------------------------------------------------------------
# per-op dispatch table
# ---------------------------------------------------------------------------

# op name -> module that registers it (imported lazily on first dispatch)
_OP_MODULES = {
    "msbfs_expand": "repro.kernels.msbfs_expand.ops",
    "msbfs_step": "repro.kernels.msbfs_expand.ops",
    "msbfs_count": "repro.kernels.msbfs_expand.ops",
    "path_overlap": "repro.kernels.path_join.ops",
    "rowwise_overlap": "repro.kernels.path_join.ops",
    "path_member": "repro.kernels.path_join.ops",
    "pairwise_popcount": "repro.kernels.pairwise_popcount.ops",
    "flash_attention": "repro.kernels.flash_attention.ops",
}

# Ops without a Pallas arm, and why. Each is a row gather over an ELL
# table: out[v] = reduce over d of src[ell_idx[v, d]]. Mosaic has no
# vector gather from a loaded value, and the source rows cannot sit in
# VMEM: the bit-packed frontier of a 2^22-vertex graph at 512 sources is
# 256 MiB (2 GiB with its 16-word rows padded to 128 lanes), against
# 128 MiB of VMEM. Left in HBM, every table entry costs one 64-byte row
# DMA, issued one by one from the scalar core. XLA's native gather serves
# the same access pattern, so these ops run their jnp arm on every
# platform. That arm walks one table column per step, so the live gather
# is (rows, W) and never (rows, D, W). The index sweep passes the
# degree-sorted sliced ELL, about one entry per arc, so a level issues
# about m descriptors; the padded (V, D) table, V*D descriptors with most
# of them on the sentinel row, remains for a delta-patched graph and for
# the enumeration's expand_level.
_ELL_GATHER = ("row gather over an ELL table; no Mosaic vector gather, "
               "source rows exceed VMEM (see registry.py)")
JNP_ONLY_OPS: dict[str, str] = {
    "msbfs_expand": _ELL_GATHER,
    "msbfs_step": _ELL_GATHER,
}

_TABLE: dict[str, dict[KernelBackend, Callable]] = {}


def register_op(name: str, *, jnp: Callable,
                pallas: Optional[Callable] = None,
                interpret: Optional[Callable] = None) -> None:
    """Register the backend implementations of one op: all three, or the
    ``jnp`` arm alone for an op listed in :data:`JNP_ONLY_OPS`."""
    if (pallas is None or interpret is None) != (name in JNP_ONLY_OPS) \
            or (pallas is None) != (interpret is None):
        raise ValueError(
            f"op {name!r}: register pallas and interpret arms together, "
            f"and only for ops not listed in JNP_ONLY_OPS")
    _TABLE[name] = {KernelBackend.JNP: jnp}
    if pallas is not None:
        _TABLE[name].update({KernelBackend.PALLAS: pallas,
                             KernelBackend.INTERPRET: interpret})


def dispatch(name: str, backend: BackendLike = None) -> Callable:
    """The implementation of op ``name`` for the resolved ``backend``
    (the ``jnp`` arm for an op in :data:`JNP_ONLY_OPS`)."""
    kb = resolve_backend(backend)
    if name in JNP_ONLY_OPS:
        kb = KernelBackend.JNP
    if name not in _TABLE:
        if name not in _OP_MODULES:
            raise KeyError(f"unknown kernel op {name!r}; registered ops: "
                           f"{registered_ops()}")
        importlib.import_module(_OP_MODULES[name])
        if name not in _TABLE:   # module imported but forgot to register
            raise KeyError(f"kernel op {name!r} not registered by "
                           f"{_OP_MODULES[name]}")
    return _TABLE[name][kb]


def registered_ops() -> list[str]:
    """Every known op name (registered or lazily registrable)."""
    return sorted(set(_TABLE) | set(_OP_MODULES))


def op_manifest() -> dict[str, str]:
    """Op name -> owning ops-module path, for every known op.

    The static analyzer (``repro.analysis.jaxpr_audit``) consumes this to
    enforce audit coverage: a newly registered op must either appear in
    the audit manifest or be explicitly listed as exempt — registering
    kernel math that no static check ever traces is itself a finding.
    """
    return dict(sorted(_OP_MODULES.items()))
