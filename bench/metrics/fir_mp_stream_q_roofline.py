"""The integer octave kernels' share of their roofline: over the window,
the least time of the step's kernel calls (``Run.kernel_least_s``) over
the time the trace shows in Mosaic kernels, in %."""


def read(run):
    if not run.traced() or run.cfg["numerics"] != "fixed":
        return None
    spent = run.kernel_s()
    if spent <= 0:
        return None
    return 100.0 * run.kernel_least_s() / spent
