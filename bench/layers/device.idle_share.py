"""device.idle_share: share of the traced call's wall interval in which no
operation ran on the device (1 - busy / window, from the profiler
trace)."""


def read(ctx):
    dev = ctx["device"]
    if dev.window_s <= 0 or dev.busy_s <= 0:
        return None
    return 1.0 - dev.busy_s / dev.window_s
