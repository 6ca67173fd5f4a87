"""Share of the traced window in which no op ran on the chip (mean over
chips): 1 - union of the device's op intervals / window."""


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t.busy_s / t.window_s)
