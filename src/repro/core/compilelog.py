"""Compile telemetry: compile counts and spans from jax's compile events.

Shape stability is the precondition for warm serving (the whole point of
the sentinel-padded pow2 buckets in ``graph.DeviceGraph``), but XLA
retraces are invisible unless you measure them — a drifting ``(m,)``
shape silently turns every post-delta batch into a cold compile. This
module turns jax's compile events into a queryable counter so warm-
compile reuse is observable in production stats and assertable in tests:

    recorder = enable()              # process-wide, idempotent
    snap = recorder.snapshot()
    ...run a batch...
    recorder.since(snap)             # {kernel_name: new compiles}
    recorder.retraces_since(snap)    # compiles of already-known kernels

Mechanism: ``jax.monitoring`` events at the start and end of each compile
phase; the outermost of a kind on a thread is a tracer span (``compile.trace``
/ ``.lower`` / ``.backend``); every backend compile counts one compile.

Definitions (shared by the engine stats and the test harness):

* a **compile** is any trace-cache miss, including the first (cold) one;
* a **retrace** is a compile of a kernel name that had already compiled
  before the observation window opened — i.e. work that warm serving
  should have reused.

jit caches are process-global, so the recorder is a process-global singleton.
"""
from __future__ import annotations

import threading
from collections import Counter
from typing import Optional

from jax import monitoring

from ..obs import trace as obstrace

__all__ = ["CompileLog", "enable", "active"]

_BACKEND = "/jax/core/compile/backend_compile_duration"
_PHASES = {"/jax/core/compile/jaxpr_trace_duration": "compile.trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower",
           _BACKEND: "compile.backend"}


class CompileLog:
    """Process-wide per-kernel compile counter (jax.monitoring listeners)."""

    def __init__(self) -> None:
        self.counts: Counter = Counter()     # kernel name -> compiles
        self._installed = False
        self._lock = threading.Lock()
        self._local = threading.local()

    def _open(self, event: str) -> list:
        # this thread's open phases of a kind: outermost span, then Nones
        return self._local.__dict__.setdefault(event, [])

    def _on_start(self, event: str, value, **kw) -> None:
        if event in _PHASES:
            stack = self._open(event)
            stack.append(None if stack else obstrace.span(
                _PHASES[event], fun=kw.get("fun_name")).__enter__())

    def _on_end(self, event: str, start, end, **kw) -> None:
        if event in _PHASES and self._open(event):
            if (sp := self._open(event).pop()) is not None:
                sp.__exit__(None, None, None)
        if event == _BACKEND:     # fun_name "jit(f)" counts for "f"
            name = str(kw.get("fun_name", "")).removeprefix("jit(")
            with self._lock:
                self.counts[name.removesuffix(")")] += 1

    def _on_event(self, event: str, **kw) -> None:
        if (event == "/jax/compilation_cache/cache_hits"
                and self._open(_BACKEND)):
            self._open(_BACKEND)[0].set(cache_hit=True)

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
        stats["n_retraces"] = self.retraces_since(snapshot)
        stats["compiled_kernels"] = new
        return stats

    # -- install -------------------------------------------------------
    def install(self) -> "CompileLog":
        if self._installed:
            return self
        monitoring.register_scalar_listener(self._on_start)
        monitoring.register_event_time_span_listener(self._on_end)
        monitoring.register_event_listener(self._on_event)
        self._installed = True
        return self

    def uninstall(self) -> None:
        if not self._installed:
            return
        monitoring.unregister_scalar_listener(self._on_start)
        monitoring.unregister_event_time_span_listener(self._on_end)
        monitoring.unregister_event_listener(self._on_event)
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
