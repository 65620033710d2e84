"""Share of the traced window in which no operation runs on the device:
one minus the union of the operations' intervals, from the profiler
trace. Layer: device. Moves ``qps``."""


def read(ctx):
    if ctx.device is None:
        return None
    return 100.0 * ctx.device.idle_share
