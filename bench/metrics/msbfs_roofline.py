"""Share of the HBM roofline that the index sweep reaches: the least bytes
the window's sweeps must move (``kernel_bytes.index_bytes``, from the
batches' shapes and the graph's valid arcs) over the device time of the
XLA program ``msbfs_dist_ell``, over the chip's HBM bandwidth. Layer:
kernels (``msbfs_step`` gather and ``msbfs_count``). Moves ``qps``."""


def read(ctx):
    dev = ctx.device
    bw = ctx.peaks.get("hbm_bytes_per_s")
    if dev is None or not bw or not dev.modules.get("msbfs_dist_ell"):
        return None
    return 100.0 * ctx.index_bytes / dev.modules["msbfs_dist_ell"] / bw
