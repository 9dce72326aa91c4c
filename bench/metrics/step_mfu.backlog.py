"""The whole step's share of the chip's peak: the operations the window's
requests required (``bench/counts``: every sample through the cascade and
one readout per request) over window x chips x the configuration's peak
(int8 for fixed, bf16 for float), in %. MXU peaks, while these kernels
add and compare on the vector unit: a bound, far below 1%."""

from bench import counts


def read(run):
    if not run.traced() or not run.sizes:
        return None
    ops = counts.step_ops(run.cfg, run.sizes)
    peak = float(run.peak[run.cfg["peak"]])
    return 100.0 * ops / (run.window_s() * run.chips * peak)
