"""Set-up: host-clock seconds from process start to the start of the
window (JAX and chip init, weights, router, opening the streams, the
warm-up that compiles or loads every program the window runs)."""


def read(run):
    return run.setup_s
