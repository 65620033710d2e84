"""Mean host-clock time of one search node, span ``enumerate.node``
(capacity planning, level loop, joins). Layer: enumeration
(``core/engine.py`` nodes, ``core/enumerate.py``, ``core/join.py``).
Moves ``qps``."""


def read(ctx):
    n = ctx.span_count("enumerate.node")
    if not n:
        return None
    return 1e3 * ctx.span_total("enumerate.node") / n
