"""Programs traced and compiled (or loaded from the persistent cache)
inside the window: jit cache misses as ``repro.core.compilelog`` counts
them: the programs that the window's fresh batches need and the
warm-up did not compile. Layer: compiler. Moves ``qps``."""


def read(ctx):
    return ctx.compiles_in_window
