"""Share of the window spent assembling micro-batches: the server's span
``serve.assemble`` (the batch's index build, the Gamma similarity and the
clustering). Layer: serving (``launch/serve.py``). Moves ``qps``."""


def read(ctx):
    if not ctx.span_count("serve.assemble"):
        return None
    return 100.0 * ctx.span_total("serve.assemble") / ctx.window_s
