"""Share of the traced window in which the device runs the index sweep,
the XLA program ``msbfs_dist_ell``. Layer: index (``core/index.py``,
``core/msbfs.py``). Moves ``qps``."""


def read(ctx):
    dev = ctx.device
    if dev is None or not dev.modules.get("msbfs_dist_ell"):
        return None
    return 100.0 * dev.modules["msbfs_dist_ell"] / dev.window_s
