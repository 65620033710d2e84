"""Share of the window spent tracing, lowering, compiling or loading
programs from the persistent cache: the union of the program's
``compile.trace``, ``compile.lower`` and ``compile.backend`` spans (opened
by ``repro.core.compilelog`` from JAX's compile events), nested spans
counted once. A traced window that compiled nothing reads 0; a program
that records no compile spans reads nothing. Layer: compiler. Moves
``qps``."""


def read(ctx):
    intervals = sorted((t0, t1) for name, t0, t1 in ctx.spans
                       if name.startswith("compile."))
    if not intervals:
        return 0.0 if ctx.spans and not ctx.compiles_in_window else None
    total, end = 0.0, float("-inf")
    for t0, t1 in intervals:
        if t1 > end:
            total += t1 - max(t0, end)
            end = t1
    return 100.0 * total / ctx.window_s
