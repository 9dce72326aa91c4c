"""Router admission: host-clock time inside ``StreamRouter.submit()`` per
packet submitted in the window, in us."""


def read(run):
    spans = run.spans.get("submit", [])
    n = sum(k for _, k in spans)
    if not n:
        return None
    return sum(s for s, _ in spans) / n * 1e6
