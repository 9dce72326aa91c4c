"""Server staging, dispatch, step and readback: host-clock time inside
``StreamRouter.drain()`` per call in the window, in ms."""


def read(run):
    spans = run.spans.get("drain", [])
    if not spans:
        return None
    return sum(s for s, _ in spans) / len(spans) * 1e3
