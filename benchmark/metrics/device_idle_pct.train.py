"""Device: the share of the traced window in which no operation ran on
the chip (1 - union of device-operation intervals / window)."""


def read(ctx):
    device = ctx["device"]
    return 100.0 * (1.0 - device["busy_s"] / device["window_s"])
