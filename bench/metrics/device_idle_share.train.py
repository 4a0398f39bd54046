"""device_idle_share.train: the share of the traced training window in
which no operation ran on a device, averaged over the cell's chips:
1 - (union of the device's op intervals) / window. Moves ``round_ms``."""


def read(ctx):
    w0, w1 = ctx.window
    devs = ctx.trace.devices[:ctx.chips]
    if not devs:
        return None
    busy = sum(ctx.trace.busy_ns(d, w0, w1) for d in devs) / len(devs)
    return 100.0 * (1.0 - busy / (w1 - w0))
