"""Session step on the device: device-busy time in the traced window per
step run, in ms."""


def read(run):
    if not run.traced() or not run.steps():
        return None
    return run.busy_s() / run.steps() * 1e3
