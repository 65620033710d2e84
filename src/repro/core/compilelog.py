"""Compile telemetry: a ``jax_log_compiles``-based retrace recorder.

Shape stability is the precondition for warm serving (the whole point of
the sentinel-padded pow2 buckets in ``graph.DeviceGraph``), but XLA
retraces are invisible unless you measure them — a drifting ``(m,)``
shape silently turns every post-delta batch into a cold compile. This
module turns jax's compile logging into a queryable counter so warm-
compile reuse is observable in production stats and assertable in tests:

    recorder = enable()              # process-wide, idempotent
    snap = recorder.snapshot()
    ...run a batch...
    recorder.since(snap)             # {kernel_name: new compiles}
    recorder.retraces_since(snap)    # compiles of already-known kernels

Mechanism: enabling flips the ``jax_log_compiles`` config flag, which
makes jax emit one ``"Compiling jit(<name>) with global shapes..."`` log
record per actual trace-cache miss (cached executions emit nothing); a
logging.Handler attached to the emitting jax loggers parses those records
into per-kernel counters. Propagation of the captured loggers is disabled
while recording so enabling telemetry does not spray compile warnings
over user output.

Definitions (shared by the engine stats and the test harness):

* a **compile** is any trace-cache miss, including the first (cold) one;
* a **retrace** is a compile of a kernel name that had already compiled
  before the observation window opened — i.e. work that warm serving
  should have reused.

jit caches are process-global, so the recorder is a process-global
singleton; like the rest of the serving stack it is not thread-safe.
"""
from __future__ import annotations

import logging
import re
from collections import Counter
from typing import Optional

__all__ = ["CompileLog", "enable", "active"]

# jax emits exactly one of these per XLA compilation when the
# jax_log_compiles flag is on, as "Compiling jit(<fn>) with global shapes
# ..." on the pxla logger; the kernel name recorded is the bare function
# name inside ``jit(...)``. The dispatch logger's "Finished tracing /
# compilation ..." records do NOT match, so each compile is counted once;
# it is captured only to keep those records off user output.
_COMPILING_RE = re.compile(r"Compiling jit\(([^\s()]+)\) with global shapes")

_JAX_LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")


class CompileLog(logging.Handler):
    """Process-wide per-kernel compile counter (a logging.Handler)."""

    def __init__(self) -> None:
        super().__init__(level=logging.DEBUG)
        self.counts: Counter = Counter()     # kernel name -> compiles
        self._installed = False
        self._saved_propagate: dict[str, bool] = {}

    # -- logging.Handler ----------------------------------------------
    def emit(self, record: logging.LogRecord) -> None:
        m = _COMPILING_RE.match(record.getMessage())
        if m:
            self.counts[m.group(1)] += 1

    # -- queries -------------------------------------------------------
    @property
    def total(self) -> int:
        return sum(self.counts.values())

    def snapshot(self) -> dict[str, int]:
        """Copy of the per-kernel counters (an observation-window mark)."""
        return dict(self.counts)

    def since(self, snapshot: dict[str, int]) -> dict[str, int]:
        """Per-kernel compiles since ``snapshot`` (only non-zero entries)."""
        out = {}
        for name, c in self.counts.items():
            d = c - snapshot.get(name, 0)
            if d > 0:
                out[name] = d
        return out

    def compiles_since(self, snapshot: dict[str, int]) -> int:
        return sum(self.since(snapshot).values())

    def retraces_since(self, snapshot: dict[str, int]) -> int:
        """Compiles of kernels that were already compiled before the
        snapshot — the warm-serving regressions, as opposed to first-time
        (cold) compiles of kernels the window introduced."""
        return sum(c for name, c in self.since(snapshot).items()
                   if snapshot.get(name, 0) > 0)

    def annotate(self, stats: dict, snapshot: dict[str, int]) -> dict:
        """Write the standard telemetry fields for one observation window
        into ``stats`` (engine run reports, delta reports, batch logs)."""
        new = self.since(snapshot)
        stats["n_compiles"] = sum(new.values())
        stats["n_retraces"] = sum(c for name, c in new.items()
                                  if snapshot.get(name, 0) > 0)
        stats["compiled_kernels"] = new
        return stats

    # -- install -------------------------------------------------------
    def install(self) -> "CompileLog":
        if self._installed:
            return self
        import jax

        for name in _JAX_LOGGERS:
            logger = logging.getLogger(name)
            self._saved_propagate[name] = logger.propagate
            logger.addHandler(self)
            logger.propagate = False     # keep compile spam off user output
            if logger.level > logging.WARNING or logger.level == 0:
                logger.setLevel(logging.WARNING)
        jax.config.update("jax_log_compiles", True)
        self._installed = True
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        import jax

        jax.config.update("jax_log_compiles", False)
        for name in _JAX_LOGGERS:
            logger = logging.getLogger(name)
            logger.removeHandler(self)
            logger.propagate = self._saved_propagate.get(name, True)
        self._installed = False


_RECORDER: Optional[CompileLog] = None


def enable() -> CompileLog:
    """Install (or return the already-installed) process-wide recorder.

    Counters are cumulative for the process lifetime — consumers take
    snapshots and diff, they never reset, so any number of engines and
    tests can share the singleton without clobbering each other.
    """
    global _RECORDER
    if _RECORDER is None:
        _RECORDER = CompileLog()
    return _RECORDER.install()


def active() -> Optional[CompileLog]:
    """The installed recorder, or None when telemetry is off."""
    return _RECORDER if (_RECORDER is not None and _RECORDER._installed) \
        else None
