"""Frontier rows the packed index sweeps gather per valid arc they relax:
the server's ``batch_log`` ``n_index_rows`` (counter
``engine_index_rows_total{layout}``) over ``n_index_arcs`` (counter
``engine_index_arcs_total``: valid arcs x hops, per sweep), summed over
the window's batches. 1 means a level gathers one row per arc; a padded
ELL reads n * (its width) / m. Layer: index (``core/index.py``,
``core/msbfs.py``). Moves ``qps``. None where the program logs no such
counters."""


def read(ctx):
    arcs = sum(b.get("n_index_arcs", 0) for b in ctx.batches)
    if not arcs:
        return None
    return sum(b["n_index_rows"] for b in ctx.batches) / arcs
