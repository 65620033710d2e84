"""Device-to-host reads per search node: the server's ``batch_log``
``n_node_syncs`` (reads in the node's capacity planning, level loop,
splice joins and capacity shrink that JAX had no host copy of; counter
``engine_host_syncs_total{stage="node"}``) over ``n_nodes`` (counter
``engine_nodes_total``). Layer: enumeration (``core/engine.py`` nodes).
Moves ``qps``."""


def read(ctx):
    nodes = sum(b.get("n_nodes", 0) for b in ctx.batches)
    if not nodes:
        return None
    return sum(b["n_node_syncs"] for b in ctx.batches) / nodes
