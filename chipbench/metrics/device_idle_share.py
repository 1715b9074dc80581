"""``device_idle_share``: the share of the traced window in which no op
ran on a device, averaged over the cell's devices.  Layer: device."""


def read(ctx):
    tr = ctx.trace
    if tr.images == 0 or tr.window_s <= 0:
        return None
    n = len(tr.devices)
    busy = sum(tr.busy_s(d) for d in range(n)) / n
    return 100.0 * (1.0 - busy / tr.window_s)
