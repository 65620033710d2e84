"""Search nodes materialized per answered query: the server's
``batch_log`` ``n_materialized`` over the window's answers. Lower means
more shared work (two half-queries per query without sharing). Layer:
planner. Moves ``qps``."""


def read(ctx):
    if not ctx.batches or not ctx.n_answered:
        return None
    return sum(b["n_materialized"] for b in ctx.batches) / ctx.n_answered
