"""Share of the window spent in shared-query detection and clustering on
the host: spans ``detect.cluster`` and ``cluster.queries``. Layer: planner
(``core/detect.py``, clustering in ``core/engine.py``). Moves ``qps``."""


def read(ctx):
    names = ("detect.cluster", "cluster.queries")
    if not any(ctx.span_count(n) for n in names):
        return None
    return 100.0 * ctx.span_total(*names) / ctx.window_s
