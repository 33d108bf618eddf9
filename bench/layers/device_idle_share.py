"""Share of the traced window in which no operation ran on the device:
one less the union of the device's op intervals over the window,
averaged over the cell's devices."""


def read(ctx):
    if not ctx.reduced.devices_found:
        return None
    return 100.0 * ctx.reduced.idle_share()
