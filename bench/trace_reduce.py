"""Reduce a ``jax.profiler`` trace to the numbers the benchmark reports.

Reads the ``.xplane.pb`` file with ``jax.profiler.ProfileData`` and keeps
three kinds of events: the device's XLA operations (``XLA Ops`` line of
each ``/device:TPU:<i>`` plane), its XLA programs (``XLA Modules`` line)
and the host's annotations named as the program names its stage spans,
dotted lower-case words such as ``serve.assemble`` (``TraceAnnotation``;
the program's spans carry them when it traces with annotations). The
window is the host annotation named by the caller. On a TPU v5e the
trace's device and host clocks agree to about 1.3 ms (a recorded trace,
``tests/data``), against windows of tens of seconds. From those:

- ``busy_s``: the union of the operation intervals inside the window,
  averaged over the devices;
- ``modules``: device seconds by XLA program name (the ``jit_`` prefix and
  the ``(<id>)`` suffix dropped), clipped to the window;
- ``gaps``: the longest idle intervals of the first device, each labelled
  with the innermost host annotation open at its midpoint.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Iterable, Optional

__all__ = ["Event", "Reduction", "load", "reduce", "reduce_dir",
           "union_length"]

_MODULE_ID = re.compile(r"\(\d+\)$")
_SPAN = re.compile(r"^[a-z_][a-z0-9_]*(\.[a-z0-9_]+)+$")


@dataclasses.dataclass(frozen=True)
class Event:
    """One trace event on a common clock, in nanoseconds. ``where`` is
    ``op`` or ``module`` on device ``device``, or ``host``."""

    where: str
    name: str
    start: float
    end: float
    device: int = 0


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float
    modules: dict
    gaps: list

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self) -> dict:
        """The ``breakdown`` of a traced run's result line: the ten XLA
        programs that took the most device time, and the ten longest idle
        gaps by the host span open in them."""
        top = sorted(self.modules.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.gaps[:10]]}


def union_length(intervals: Iterable[tuple[float, float]]) -> float:
    """Total length covered by ``intervals`` (overlaps counted once)."""
    total, cur_lo, cur_hi = 0.0, None, None
    for lo, hi in sorted(intervals):
        if cur_hi is None or lo > cur_hi:
            if cur_hi is not None:
                total += cur_hi - cur_lo
            cur_lo, cur_hi = lo, hi
        else:
            cur_hi = max(cur_hi, hi)
    if cur_hi is not None:
        total += cur_hi - cur_lo
    return total


def _module_name(name: str) -> str:
    name = _MODULE_ID.sub("", name)
    return name[4:] if name.startswith("jit_") else name


def load(path: Path) -> list[Event]:
    """The device operations, device programs and host annotations of one
    ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    out = []
    for plane in pd.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                where = {"XLA Ops": "op", "XLA Modules": "module"}.get(
                    line.name)
                if where is None:
                    continue
                for e in line.events:
                    out.append(Event(where, e.name, e.start_ns,
                                     e.start_ns + e.duration_ns, dev))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0 and _SPAN.match(e.name):
                        out.append(Event("host", e.name, e.start_ns,
                                         e.start_ns + e.duration_ns))
    return out


def reduce(events: list[Event], window_span: str, n_gaps: int = 10
           ) -> Optional[Reduction]:
    """Reduce ``events`` over the host annotation ``window_span``; None
    when the trace holds no such annotation or no device operation."""
    wins = [e for e in events if e.where == "host" and e.name == window_span]
    ops = [e for e in events if e.where == "op"]
    if not wins or not ops:
        return None
    w0 = min(e.start for e in wins)
    w1 = max(e.end for e in wins)

    def clip(e):
        return max(e.start, w0), min(e.end, w1)

    devices = sorted({e.device for e in ops})
    busy = {d: union_length(clip(e) for e in ops
                            if e.device == d and e.end > w0 and e.start < w1)
            for d in devices}
    modules: dict = {}
    for e in events:
        if e.where == "module" and e.end > w0 and e.start < w1:
            lo, hi = clip(e)
            name = _module_name(e.name)
            modules[name] = modules.get(name, 0.0) + (hi - lo) / 1e9
    # idle gaps of the first device, labelled by the host annotation open
    gaps = []
    cursor = w0
    first = sorted(clip(e) for e in ops if e.device == devices[0]
                   and e.end > w0 and e.start < w1)
    for lo, hi in first + [(w1, w1)]:
        if lo > cursor:
            gaps.append((cursor, lo))
        cursor = max(cursor, hi)
    gaps.sort(key=lambda g: g[0] - g[1])
    hosts = [e for e in events if e.where == "host" and e.name != window_span]
    labelled = []
    for lo, hi in gaps[:n_gaps]:
        mid = (lo + hi) / 2
        open_ = [e for e in hosts if e.start <= mid < e.end]
        label = (min(open_, key=lambda e: e.end - e.start).name if open_
                 else "no host span")
        labelled.append((label, (hi - lo) / 1e9))
    return Reduction(window_s=(w1 - w0) / 1e9,
                     busy_s=sum(busy.values()) / len(devices) / 1e9,
                     modules=modules, gaps=labelled)


def reduce_dir(trace_dir: Path, window_span: str) -> Optional[Reduction]:
    """Reduce the newest ``.xplane.pb`` under a ``jax.profiler`` logdir."""
    files = sorted(Path(trace_dir).glob("plugins/profile/*/*.xplane.pb"))
    if not files:
        return None
    return reduce(load(files[-1]), window_span)
