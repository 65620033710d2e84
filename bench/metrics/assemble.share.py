"""Share of the window spent assembling answers: spans ``assemble.query``
(the final join per query) and ``transfer.paths`` (the host copy of a
path matrix). Layer: assembly (``core/engine.py``, ``core/query.py``).
Moves ``qps``."""


def read(ctx):
    names = ("assemble.query", "transfer.paths")
    if not any(ctx.span_count(n) for n in names):
        return None
    return 100.0 * ctx.span_total(*names) / ctx.window_s
