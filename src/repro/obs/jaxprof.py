"""Opt-in ``jax.profiler`` integration for :mod:`repro.obs`.

Three capabilities:

* **Span annotations on the device timeline** — :func:`attach` installs a
  ``jax.profiler.TraceAnnotation`` factory on a tracer, so every host
  span also shows up as a named region in a ``start_trace``-captured
  profile (TensorBoard / Perfetto), lining host stages up against the
  XLA device timeline. ``trace.enable(annotate=True)`` does this for the
  process tracer. Inside jitted code, per-level attribution instead
  comes from ``jax.named_scope`` metadata (see ``core/msbfs.py``) —
  named scopes ride the HLO op names and add no jaxpr equations, so the
  committed dispatch budgets are unaffected.
* **Whole-run capture** — :func:`start_trace` / :func:`stop_trace` (or
  the :func:`profile_run` context manager) bracket a run with the XLA
  profiler writing to a TensorBoard logdir; ``serve --jax-profile DIR``
  wires this around the streaming loop.
* **Device-memory sampling** — :func:`sample_device_memory` reads
  ``device.memory_stats()`` into the ``device_bytes_in_use`` gauge
  (labeled per device). The CPU backend reports no memory stats; the
  function then returns ``None``.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax
import jax.profiler as prof

from . import metrics as _metrics
from . import trace as _trace

__all__ = ["annotation_factory", "attach", "detach", "start_trace",
           "stop_trace", "profile_run", "sample_device_memory"]


def annotation_factory():
    """The ``name -> context manager`` factory for span annotation."""
    return prof.TraceAnnotation


def attach(tracer: Optional[_trace.Tracer] = None) -> _trace.Tracer:
    """Install the annotation factory on ``tracer`` (default: the process
    tracer), so recorded spans also appear on profiler timelines."""
    tr = tracer if tracer is not None else _trace.tracer()
    tr.annotator = annotation_factory()
    return tr


def detach(tracer: Optional[_trace.Tracer] = None) -> _trace.Tracer:
    tr = tracer if tracer is not None else _trace.tracer()
    tr.annotator = None
    return tr


def start_trace(logdir: str) -> None:
    """Start an XLA profiler capture into a TensorBoard logdir."""
    prof.start_trace(logdir)


def stop_trace() -> None:
    prof.stop_trace()


@contextlib.contextmanager
def profile_run(logdir: Optional[str]):
    """Bracket a block with start/stop_trace when ``logdir`` is set."""
    if not logdir:
        yield False
        return
    start_trace(logdir)
    try:
        yield True
    finally:
        stop_trace()


def sample_device_memory(reg: Optional[_metrics.MetricsRegistry] = None
                         ) -> Optional[int]:
    """Sample per-device bytes-in-use into ``device_bytes_in_use`` gauges.

    Returns the total bytes across devices, or ``None`` when no device
    reports memory stats (the CPU backend).
    """
    reg = reg if reg is not None else _metrics.registry()
    total = None
    for d in jax.devices():
        stats = d.memory_stats()
        if not stats or "bytes_in_use" not in stats:
            continue
        used = int(stats["bytes_in_use"])
        reg.gauge("device_bytes_in_use", device=str(d)).set(used)
        total = (total or 0) + used
    return total
