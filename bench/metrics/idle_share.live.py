"""The device's idle share of the traced window: 1 - union of its
operations' intervals / window, averaged over the chips, in %."""


def read(run):
    if not run.traced():
        return None
    return 100.0 * (1.0 - run.busy_s() / run.window_s())
