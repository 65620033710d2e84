"""Device-to-host reads of answer assembly per answered query: the
server's ``batch_log`` ``n_assemble_syncs`` (counts, completions and join
overflow flags read in per-query assembly; counter
``engine_host_syncs_total{stage="assemble"}``) over the window's answers.
Layer: assembly (``core/engine.py``). Moves ``qps``."""


def read(ctx):
    if not ctx.n_answered or not any("n_assemble_syncs" in b
                                     for b in ctx.batches):
        return None
    return (sum(b["n_assemble_syncs"] for b in ctx.batches)
            / ctx.n_answered)
