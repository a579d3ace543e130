"""device_idle_share (%): 1 - busy / window over the traced part of the
window, busy being the union of the device's op intervals."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
