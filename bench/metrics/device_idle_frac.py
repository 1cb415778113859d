"""Share of the traced window, in percent, in which no op ran on the
device (mean over the cell's chips)."""


def read(run):
    if run.window_s <= 0 or not run.device_ops():
        return None
    return 100.0 * (1.0 - run.busy_s() / run.window_s)
