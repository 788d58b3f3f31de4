"""Device: the share of the traced window in which no operation ran on the
device, in percent."""


def read(trace, record):
    if trace.window_s <= 0 or trace.devices == 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)
