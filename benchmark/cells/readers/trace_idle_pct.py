"""Reader: the device's idle share of the traced stretch:
1 - union of device-operation intervals / traced window."""


def read(run, args):
    if not run.device_busy_s or not run.trace_window_s:
        return None
    return 100.0 * (1.0 - run.device_busy_s / run.trace_window_s)
