"""Share of the traced window in which no operation ran on the device, as
a mean over the cell's devices."""


def read(view):
    tr = view["trace"]
    return 100.0 * (1.0 - tr.mean_busy_s / tr.window_s)
