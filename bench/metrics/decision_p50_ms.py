"""The median, over every packet due in the window, of the latency from
its due time to the return of the ``drain()`` that decided it, in ms; a
packet never decided counts as infinitely late."""

from bench import stats


def read(run):
    return stats.due_latency_ms(run.result, 50)
