"""Audio samples whose decisions came back within the window, over the
window's host-clock length: every stream, rotation stalls included."""


def read(run):
    return run.result["samples"] / run.result["window_s"]
