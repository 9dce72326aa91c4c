"""How late the load generator submitted: the 99th percentile, over the
window's packets, of submit time minus due time (host clock), in ms. A
starved generator shows here and not as a slow server."""

from bench import stats


def read(run):
    r = run.result
    if "due" not in r or r["due"].size == 0:
        return None
    return stats.percentile((r["submit"] - r["due"]) * 1e3, 99)
