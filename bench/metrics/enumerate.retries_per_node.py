"""Overflow re-runs per search node: the server's ``batch_log``
``n_retries`` (node re-runs with every level capacity times 4, and join
capacity retries; counter ``engine_retries_total``) over ``n_nodes``.
Layer: enumeration (``core/engine.py`` nodes and joins). Moves ``qps``."""


def read(ctx):
    nodes = sum(b.get("n_nodes", 0) for b in ctx.batches)
    if not nodes:
        return None
    return sum(b["n_retries"] for b in ctx.batches) / nodes
