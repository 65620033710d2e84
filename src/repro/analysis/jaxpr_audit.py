"""Layer 2: jaxpr audit of the registered hot-function manifest.

For every entry in :data:`MANIFEST` (the engine's per-level hot
functions), per backend, this module traces the function with
``jax.make_jaxpr`` on tiny concrete shapes and statically verifies:

  audit/trace      the function traces at all — a ``.item()``/``int()``
                   host sync inside jitted code surfaces here as a
                   ConcretizationTypeError, before any benchmark runs
  audit/callback   zero host-callback primitives in the jaxpr
                   (io_callback, pure_callback, debug_callback, ...)
  audit/budget     per-level (or total) jaxpr-eqn counts at or below the
                   committed ``benchmarks/baselines/DISPATCH_BUDGETS.json``
                   — the PR 6 eqn accounting, now a checked-in contract
                   (a pallas_call counts as ONE eqn: one fused dispatch);
                   kernel backends additionally pin pallas dispatches per
                   level (the fused MS-BFS step must stay at 1)
  audit/int8       the int8 distance dtype is proven in range: INF for
                   the K_MAX_INT8 ceiling fits with headroom, and an
                   out-of-range ``k_max`` raises ValueError instead of
                   clamping
  audit/retrace    a second execution on same-shape, different-value
                   inputs adds zero compiles (compilelog) — shape may
                   not depend on any non-static argument
  audit/coverage   every op in ``kernels.registry.op_manifest()`` is
                   either traced by some manifest entry or explicitly
                   exempted with a written reason

Per-level counts are measured as a finite difference: trace at level L
and L+1, ``per_level = eqns(L+1) - eqns(L)``, ``base = eqns(L) - L *
per_level`` — robust to constant setup/teardown around the hop loop.
"""
from __future__ import annotations

import dataclasses
import json
from functools import partial
from pathlib import Path
from typing import Callable, Dict, Optional, Sequence, Tuple

from .report import AnalysisReport, Finding

__all__ = ["MANIFEST", "AUDIT_EXEMPT_OPS", "HotFn", "run_audit",
           "audit_traceable", "measure_budgets", "DEFAULT_BUDGETS_PATH"]

DEFAULT_BUDGETS_PATH = Path("benchmarks/baselines/DISPATCH_BUDGETS.json")

# level knob values used for the finite-difference measurement
_LEVELS = (2, 3)


@dataclasses.dataclass(frozen=True)
class HotFn:
    """One audited hot function.

    ``make(backend, level)`` returns ``(fn, args)`` ready for
    ``jax.make_jaxpr(fn)(*args)`` / ``fn(*args)`` on tiny shapes; for
    unleveled entries the ``level`` argument is ignored.
    """
    name: str
    backends: Tuple[str, ...]
    make: Callable[[str, int], tuple]
    leveled: bool = True
    # entries whose inputs cannot be value-perturbed for the retrace
    # check (e.g. sorted-side invariants) may opt out with a reason
    retrace: bool = True


# ---------------------------------------------------------------------------
# manifest
# ---------------------------------------------------------------------------

def _mk_msbfs_dist(backend: str, k: int):
    import jax.numpy as jnp
    from ..core.msbfs import msbfs_dist
    n, m, S = 16, 8, 4
    esrc = jnp.zeros((m,), jnp.int32)
    edst = jnp.zeros((m,), jnp.int32)
    srcs = jnp.zeros((S,), jnp.int32)
    return (lambda a, b, c: msbfs_dist(a, b, c, n=n, k_max=k),
            (esrc, edst, srcs))


def _mk_msbfs_set_dist(backend: str, k: int):
    import jax.numpy as jnp
    from ..core.msbfs import msbfs_set_dist
    n, m = 16, 8
    esrc = jnp.zeros((m,), jnp.int32)
    edst = jnp.zeros((m,), jnp.int32)
    seed = jnp.zeros((n + 1,), jnp.int8)
    return (lambda a, b, c: msbfs_set_dist(a, b, c, n=n, k_max=k),
            (esrc, edst, seed))


def _sweep_table(sliced: bool, n: int = 16, D: int = 4):
    """A padded (n+1, D) ELL, or a sliced ELL of the same n rows: four
    tables of widths 4, 2, 1 and 0 (the last a degree-0 tail)."""
    import jax.numpy as jnp
    from ..core.graph import SlicedEll
    if not sliced:
        return jnp.full((n + 1, D), n, jnp.int32)
    rows = (2, 4, 6, n - 12)
    return SlicedEll(jnp.arange(n, dtype=jnp.int32),
                     jnp.arange(n, dtype=jnp.int32),
                     tuple(jnp.full((r, w), n, jnp.int32)
                           for r, w in zip(rows, (D, 2, 1, 0))))


def _mk_msbfs_dist_ell(backend: str, k: int, sliced: bool = False):
    import jax.numpy as jnp
    from ..core.msbfs import msbfs_dist_ell
    n, S = 16, 4
    srcs = jnp.zeros((S,), jnp.int32)
    return (lambda a, b: msbfs_dist_ell(a, b, n=n, k_max=k, backend=backend),
            (_sweep_table(sliced, n), srcs))


def _mk_msbfs_set_dist_ell(backend: str, k: int, sliced: bool = False):
    import jax.numpy as jnp
    from ..core.msbfs import msbfs_set_dist_ell
    n = 16
    seed = jnp.zeros((n + 1,), jnp.int8)
    return (lambda a, b: msbfs_set_dist_ell(a, b, n=n, k_max=k,
                                            backend=backend),
            (_sweep_table(sliced, n), seed))


def _mk_expand_level(backend: str, k: int):
    import jax.numpy as jnp
    from ..core.enumerate import expand_level
    n, D, cap, L = 16, 4, 8, 6
    verts = jnp.zeros((cap, L), jnp.int32)
    ell = jnp.full((n, D), n, jnp.int32)
    tbl = jnp.zeros((n + 1, 2), jnp.int8)
    return (lambda v, c, e, t, s: expand_level(
                v, c, e, t, s, level=1, budget=4, out_cap=cap,
                backend=backend),
            (verts, jnp.int32(2), ell, tbl, jnp.int32(-2)))


def _join_sides():
    import jax.numpy as jnp
    cap, L = 8, 6
    verts = jnp.zeros((cap, L), jnp.int32)
    keys = jnp.zeros((cap,), jnp.int32)
    return verts, keys, jnp.int32(2)


def _mk_keyed_join(backend: str, k: int):
    from ..core.join import SortedSide, keyed_join
    verts, keys, count = _join_sides()
    return (lambda av, ak, ac, bv, bc: keyed_join(
                SortedSide(av, ak, ac), bv, bc, a_col=2, b_col=2,
                out_cap=8, out_width=6, backend=backend),
            (verts, keys, count, verts, count))


def _mk_keyed_join_count(backend: str, k: int):
    from ..core.join import SortedSide, keyed_join_count
    verts, keys, count = _join_sides()
    return (lambda av, ak, ac, bv, bc: keyed_join_count(
                SortedSide(av, ak, ac), bv, bc, a_col=2, b_col=2,
                pair_cap=8, backend=backend),
            (verts, keys, count, verts, count))


def _mk_cross_join(backend: str, k: int):
    from ..core.join import cross_join
    verts, _, count = _join_sides()
    return (lambda pv, pc, cv, cc: cross_join(
                pv, pc, cv, cc, p_col=2, c_col=2, out_cap=8, out_width=6,
                backend=backend),
            (verts, count, verts, count))


MANIFEST: Tuple[HotFn, ...] = (
    HotFn("msbfs_dist", ("jnp",), _mk_msbfs_dist),
    HotFn("msbfs_set_dist", ("jnp",), _mk_msbfs_set_dist),
    HotFn("msbfs_dist_ell", ("jnp", "interpret"), _mk_msbfs_dist_ell),
    HotFn("msbfs_set_dist_ell", ("jnp", "interpret"), _mk_msbfs_set_dist_ell),
    # the sliced ELL a full DeviceGraph.build gives the index sweep (the
    # two entries above: the padded ELL of a delta-patched graph)
    HotFn("msbfs_dist_ell_sliced", ("jnp", "interpret"),
          partial(_mk_msbfs_dist_ell, sliced=True)),
    HotFn("msbfs_set_dist_ell_sliced", ("jnp", "interpret"),
          partial(_mk_msbfs_set_dist_ell, sliced=True)),
    HotFn("expand_level", ("jnp", "interpret"), _mk_expand_level,
          leveled=False),
    HotFn("keyed_join", ("jnp", "interpret"), _mk_keyed_join, leveled=False),
    HotFn("keyed_join_count", ("jnp", "interpret"), _mk_keyed_join_count,
          leveled=False),
    HotFn("cross_join", ("jnp", "interpret"), _mk_cross_join, leveled=False),
)

# registry ops deliberately not traced by the manifest. Every op in
# kernels.registry.op_manifest() must be either reached by a MANIFEST
# entry (see _OPS_COVERED) or listed here with a reason — silently
# unaudited kernel math is an audit/coverage finding.
AUDIT_EXEMPT_OPS: Dict[str, str] = {
    "msbfs_expand": "single-hop building block of msbfs_step (which the "
                    "msbfs_*_ell entries reach); pinned against numpy by "
                    "tests/test_kernels.py",
    "path_overlap": "pairwise path-similarity op used by host-side "
                    "clustering tooling, not the per-level enumeration "
                    "loop; parity pinned by tests/test_kernels.py",
    "pairwise_popcount": "host-side similarity-matrix batch op (one "
                         "dispatch per batch, not per level); parity "
                         "pinned by tests/test_similarity_clustering.py",
    "flash_attention": "model-serving sidecar (models/transformer), not "
                       "on the HC-s-t query path",
}

# ops each manifest entry's kernel arms route through (for coverage)
_OPS_COVERED = {"msbfs_step", "msbfs_count", "rowwise_overlap",
                "path_member"}


# ---------------------------------------------------------------------------
# jaxpr scans
# ---------------------------------------------------------------------------

def _scan_callbacks(jaxpr, acc: set) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if "callback" in name or name in ("infeed", "outfeed"):
            acc.add(name)
        if name == "pallas_call":
            continue
        for val in eqn.params.values():
            for v in (val if isinstance(val, (tuple, list)) else [val]):
                if hasattr(v, "jaxpr"):
                    _scan_callbacks(v.jaxpr, acc)
                elif hasattr(v, "eqns"):
                    _scan_callbacks(v, acc)


def _kernel_dispatches(jaxpr) -> int:
    total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            total += 1
            continue
        for val in eqn.params.values():
            for v in (val if isinstance(val, (tuple, list)) else [val]):
                if hasattr(v, "jaxpr"):
                    total += _kernel_dispatches(v.jaxpr)
                elif hasattr(v, "eqns"):
                    total += _kernel_dispatches(v)
    return total


def audit_traceable(fn: Callable, args: Sequence, *,
                    name: str) -> list:
    """Trace ``fn(*args)`` and return findings for trace failures (host
    syncs surface as ConcretizationTypeError) and callback primitives.
    Exposed for the analyzer's self-tests (seed a ``.item()`` into a toy
    fn and assert detection)."""
    import jax
    findings = []
    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as exc:
        findings.append(Finding(
            "audit/trace", name, 0,
            f"failed to trace: {type(exc).__name__}: "
            f"{str(exc).splitlines()[0][:200]} (host sync inside the "
            f"traced region?)"))
        return findings
    cbs: set = set()
    _scan_callbacks(closed.jaxpr, cbs)
    if cbs:
        findings.append(Finding(
            "audit/callback", name, 0,
            f"host callback primitive(s) in jaxpr: {sorted(cbs)}"))
    return findings


# ---------------------------------------------------------------------------
# measurement + checks
# ---------------------------------------------------------------------------

def _measure_entry(entry: HotFn, backend: str) -> Dict[str, int]:
    """Measured dispatch stats for one (entry, backend) cell."""
    import jax
    from ..launch.hlo_analysis import count_eqns
    if entry.leveled:
        lo, hi = _LEVELS
        f_lo, a_lo = entry.make(backend, lo)
        f_hi, a_hi = entry.make(backend, hi)
        e_lo = count_eqns(jax.make_jaxpr(f_lo)(*a_lo).jaxpr)
        jx_hi = jax.make_jaxpr(f_hi)(*a_hi)
        e_hi = count_eqns(jx_hi.jaxpr)
        per = e_hi - e_lo
        stats = {"eqns_per_level": per, "base_eqns": e_lo - lo * per}
        if backend != "jnp":
            k_lo = _kernel_dispatches(jax.make_jaxpr(f_lo)(*a_lo).jaxpr)
            k_hi = _kernel_dispatches(jx_hi.jaxpr)
            stats["kernel_dispatches_per_level"] = k_hi - k_lo
    else:
        fn, args = entry.make(backend, _LEVELS[0])
        jx = jax.make_jaxpr(fn)(*args)
        stats = {"total_eqns": count_eqns(jx.jaxpr)}
        if backend != "jnp":
            stats["kernel_dispatches"] = _kernel_dispatches(jx.jaxpr)
    return stats


def measure_budgets() -> Dict[str, Dict[str, Dict[str, int]]]:
    """Measured dispatch stats for the full manifest (the budget-update
    workflow: ``python -m repro.analysis --write-budgets`` commits this)."""
    return {e.name: {b: _measure_entry(e, b) for b in e.backends}
            for e in MANIFEST}


def _check_budget(name: str, backend: str, stats: Dict[str, int],
                  budget: Optional[Dict[str, int]]) -> list:
    loc = f"{name}[{backend}]"
    if budget is None:
        return [Finding("audit/budget", loc, 0,
                        f"no committed budget in DISPATCH_BUDGETS.json "
                        f"(measured: {stats}); run --write-budgets and "
                        f"commit the baseline")]
    findings = []
    for key, actual in stats.items():
        allowed = budget.get(key)
        if allowed is None:
            findings.append(Finding(
                "audit/budget", loc, 0,
                f"budget entry missing key {key!r} (measured {actual})"))
        elif actual > allowed:
            findings.append(Finding(
                "audit/budget", loc, 0,
                f"{key} regressed: measured {actual} > committed budget "
                f"{allowed}"))
    return findings


def _check_int8(report: AnalysisReport) -> None:
    """int8 overflow hazards proven in range, not just clamped."""
    import jax.numpy as jnp
    from ..core import msbfs

    inf = msbfs.INF_FOR(msbfs.K_MAX_INT8)
    headroom = 127 - inf
    if inf > 127 or headroom < 1:
        report.add([Finding(
            "audit/int8", "msbfs.K_MAX_INT8", 0,
            f"INF_FOR(K_MAX_INT8)={inf} leaves headroom={headroom} in "
            f"int8 — the sentinel no longer fits")])
    report.meta["int8"] = {"k_max_ceiling": msbfs.K_MAX_INT8,
                          "inf": inf, "headroom": headroom}

    # the guard must RAISE for k_max past the ceiling (naming k_max), not
    # silently clamp
    n = 4
    ell = jnp.full((n + 1, 2), n, jnp.int32)
    seed = jnp.zeros((n + 1,), jnp.int8)
    for fn_name, call in (
        ("msbfs_set_dist", lambda k: msbfs.msbfs_set_dist(
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32), seed,
            n=n, k_max=k)),
        ("msbfs_set_dist_ell", lambda k: msbfs.msbfs_set_dist_ell(
            ell, seed, n=n, k_max=k)),
    ):
        try:
            call(msbfs.K_MAX_INT8 + 1)
            report.add([Finding(
                "audit/int8", fn_name, 0,
                f"k_max={msbfs.K_MAX_INT8 + 1} did not raise — the int8 "
                f"bound is clamped, not checked")])
        except ValueError as exc:
            if "k_max" not in str(exc):
                report.add([Finding(
                    "audit/int8", fn_name, 0,
                    f"out-of-range k_max raised but the error does not "
                    f"name k_max: {exc}")])


def _perturb(args):
    """Same-shape, different-value variants of the example args (zeros of
    index arrays stay in range)."""
    import jax
    import jax.numpy as jnp

    def bump(x):
        if hasattr(x, "dtype") and x.ndim == 0:
            return x          # scalar knobs (counts/stop) keep semantics
        if hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.integer):
            return x * 0      # index arrays: all-zeros is always in range
        if hasattr(x, "dtype"):
            return x * 0
        return x
    return jax.tree_util.tree_map(bump, tuple(args))


def _check_retrace(entry: HotFn, backend: str) -> list:
    """Second same-shape execution must add zero compiles."""
    import jax
    from ..core import compilelog
    log = compilelog.enable()
    fn, args = entry.make(backend, _LEVELS[0])
    loc = f"{entry.name}[{backend}]"
    try:
        # materialize the perturbed args BEFORE the snapshot — building
        # them dispatches tiny jitted muls whose compiles must not be
        # attributed to the re-run
        args2 = jax.block_until_ready(_perturb(args))
        fn(*args)                       # warm (may compile)
        snap = log.snapshot()
        fn(*args2)                      # same shapes, new values
    except Exception as exc:  # trace check already reported the cause
        return [Finding("audit/retrace", loc, 0,
                        f"execution failed: {type(exc).__name__}: "
                        f"{str(exc).splitlines()[0][:160]}")]
    new = log.compiles_since(snap)
    if new:
        return [Finding(
            "audit/retrace", loc, 0,
            f"{new} new compile(s) on a same-shape re-run — output shape "
            f"or trace depends on a non-static argument value")]
    return []


def _check_coverage() -> list:
    from ..kernels.registry import op_manifest
    findings = []
    for op in op_manifest():
        if op in _OPS_COVERED or op in AUDIT_EXEMPT_OPS:
            continue
        findings.append(Finding(
            "audit/coverage", f"registry:{op}", 0,
            f"registered kernel op {op!r} is neither traced by the audit "
            f"manifest nor listed in AUDIT_EXEMPT_OPS with a reason"))
    stale = sorted(set(AUDIT_EXEMPT_OPS) - set(op_manifest()))
    for op in stale:
        findings.append(Finding(
            "audit/coverage", f"registry:{op}", 0,
            f"AUDIT_EXEMPT_OPS lists {op!r} which is no longer a "
            f"registered op — drop the stale exemption"))
    return findings


def run_audit(budgets_path: Optional[Path] = None, *,
              check_budgets: bool = True,
              check_retraces: bool = True) -> AnalysisReport:
    """Run the full layer-2 audit; returns one :class:`AnalysisReport`.

    ``budgets_path=None`` with ``check_budgets=True`` reads
    :data:`DEFAULT_BUDGETS_PATH` (relative to the current directory);
    a missing file reports one finding per audited cell.
    """
    report = AnalysisReport()
    budgets: Dict = {}
    if check_budgets:
        path = Path(budgets_path or DEFAULT_BUDGETS_PATH)
        if path.exists():
            budgets = {k: v for k, v in
                       json.loads(path.read_text()).items()
                       if not k.startswith("_")}
        else:
            report.add([Finding(
                "audit/budget", str(path), 0,
                "committed budget baseline not found — run "
                "`python -m repro.analysis --write-budgets` and commit it")])
            check_budgets = False

    measured: Dict[str, Dict[str, Dict[str, int]]] = {}
    for entry in MANIFEST:
        for backend in entry.backends:
            report.n_functions += 1
            loc = f"{entry.name}[{backend}]"
            fn, args = entry.make(backend, _LEVELS[0])
            trace_findings = audit_traceable(fn, args, name=loc)
            report.add(trace_findings)
            if any(f.rule == "audit/trace" for f in trace_findings):
                continue            # can't measure what doesn't trace
            stats = _measure_entry(entry, backend)
            measured.setdefault(entry.name, {})[backend] = stats
            if check_budgets:
                report.add(_check_budget(entry.name, backend, stats,
                                         budgets.get(entry.name, {})
                                         .get(backend)))
            if check_retraces and entry.retrace:
                report.add(_check_retrace(entry, backend))

    _check_int8(report)
    report.add(_check_coverage())
    report.meta["measured"] = measured
    return report
