"""Opt-in ``jax.profiler`` integration for :mod:`repro.obs`.

Two capabilities:

* **Span annotations on the device timeline** — ``trace.enable(
  annotate=True)`` installs :func:`annotation_factory` (a
  ``jax.profiler.TraceAnnotation`` factory) on the process tracer, so
  every recorded span also shows up as a named region in a
  ``start_trace``-captured profile (TensorBoard / Perfetto), lining host
  stages up against the XLA device timeline. Inside jitted code,
  per-level attribution instead comes from ``jax.named_scope`` metadata
  (see ``core/msbfs.py``) — named scopes ride the HLO op names and add
  no jaxpr equations, so the committed dispatch budgets are unaffected.
* **Whole-run capture** — :func:`start_trace` / :func:`stop_trace` (or
  the :func:`profile_run` context manager) bracket a run with the XLA
  profiler writing to a TensorBoard logdir; ``serve --jax-profile DIR``
  wires this around the streaming loop.
"""
from __future__ import annotations

import contextlib
from typing import Optional

import jax.profiler as prof

__all__ = ["annotation_factory", "start_trace", "stop_trace", "profile_run"]


def annotation_factory():
    """The ``name -> context manager`` factory for span annotation."""
    return prof.TraceAnnotation


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
